"""The Pallas fused-kernel tier: cost-model alternatives the search elects.

These TPU kernels replace hot composed-XLA-op paths when — and only
when — they are elected: by the Strategy IR's ``kernel`` slot, or where
they are called, from what the call observes (:data:`OBSERVED_KERNELS`;
the decode-attention kernels by the cache layout,
``serving.kv_cache.layout_for``) — a calibratable crossover decision,
never an unconditional swap; the hierarchical placement results of arxiv
2110.10548 say the win is topology-dependent, and the round-3
flash-crossover measurements say it is shape-dependent too:

* :func:`~autodist_tpu.kernel.pallas.flash_decode
  .flash_decode_attention_dense` — single-query-per-slot block-streaming
  attention over the whole TP-sharded dense KV cache (online softmax,
  masked slot lengths, a slot's live blocks only, the step's rows written
  on the way), the decode analog of ``ops/flash_attention.py`` and the
  kernel that lets ``ServingEngine`` accept ``attention_fn``; elected by
  :func:`~autodist_tpu.kernel.pallas.flash_decode.dense_decode_elected`.
  Its siblings: ``flash_decode_attention_latent`` over a cache of
  latent-attention rows (``latent_decode_elected``) and
  ``flash_decode_attention_paged`` over a block pool through its table.
* :func:`~autodist_tpu.kernel.pallas.flash_prefill
  .flash_prefill_attention_paged` — a chunk of prompt rows against the
  paged cache, the page walk of the paged decode kernel with a window of
  queries.
* :func:`~autodist_tpu.kernel.pallas.quant_ring.quantized_ring_all_reduce`
  — the EQuARX-style fused quantize-into-all-reduce (PAPERS.md
  2506.17615): quantize/dequantize happens *per hop inside the ring
  step* and the wire carries TRUE ``s8`` chunks, replacing the
  convert-sandwich ``kernel/quantize.py`` wraps around one monolithic
  fp16-wire collective — a form composed HLO cannot express.
* :func:`~autodist_tpu.kernel.pallas.collective_matmul
  .collective_matmul_row_fused` — the ``ppermute``-chunked row-parallel
  matmul of ``parallel/tensor.py collective_matmul_row`` with the hop
  accumulate + chunk matmul fused into one kernel pass.
* :func:`~autodist_tpu.kernel.pallas.a2a_ring.quantized_ring_all_to_all`
  — the quant_ring generalized from reduce to permute: the MoE
  dispatch/combine ``all_to_all`` rewritten as a ``ppermute`` rotation
  ring whose every hop carries a TRUE ``s8`` chunk + fp32 scale, with
  the q/dq fused into the hop (no convert sandwich around one
  monolithic collective).

* :func:`~autodist_tpu.kernel.pallas.delta_step.gated_delta_step_fused`
  — one position of the gated delta rule over the serving cache
  manager's stacked float32 recurrent state, each ``(slot, head)`` tile
  read once and written back in place; elected where it is called, from
  what the call observes (:data:`OBSERVED_KERNELS`).
* :func:`~autodist_tpu.kernel.pallas.retention_step.retention_step_fused`
  — one position of power retention over the same manager's state of
  ``[offsets, 128, 128]`` tiles a key/value head, a slab of offsets a
  grid step, the group's read-outs summed across the steps; elected
  where it is called too.
* :func:`~autodist_tpu.kernel.pallas.ssd_step.ssd_step_fused` — one
  position of a Mamba-2 state-space layer over the same manager's state,
  a group's heads ONE ``[N, heads * P]`` matrix (the key and the query
  shared, the decay a row), read once and written back in place; elected
  where it is called too.
* :func:`~autodist_tpu.kernel.pallas.grouped_matmul.grouped_matmul` — a
  decode step's sorted (row, expert) pairs through the held experts that
  have rows, gate/up, SiLU and down in one call, each expert's weights
  streamed once; elected where it is called too.

Every kernel runs under the Pallas interpreter off-TPU (the simulated
CPU mesh the test harness uses), so each carries a CPU golden pinned
against its composed lowering; on real TPU the same ``pallas_call``
compiles through Mosaic.  Each call site is wrapped in a
``jax.named_scope`` whose :func:`kernel_marker` string survives into
optimized-HLO op metadata — the structural evidence the ADT120 program
rule (``fused_kernel_replaced``) keys on to prove an elected kernel
actually replaced the composed op soup.
"""
from __future__ import annotations

# The Strategy IR's kernel-slot vocabulary (strategy/ir.py
# normalize_kernel re-exports this; kernel code stays IR-agnostic).
KERNEL_CHOICES = ("flash_decode", "flash_prefill", "quant_ring",
                  "collective_matmul", "a2a_ring", "flash_attention",
                  "delta_step", "grouped_matmul", "retention_step",
                  "ssd_step")

# Kernels that change the *training* program (the pipeline and expert
# lowerings honor them); flash_decode/flash_prefill are serving-side
# (the decode and chunked-prefill programs).
TRAINING_KERNELS = ("quant_ring", "collective_matmul", "a2a_ring",
                    "flash_attention")

# Kernels elected where they are called, from what the call observes
# (``models.transformer.attend``; ``serving.kv_cache.DenseLayout
# .advance_state``, ``.advance_retention`` and ``.advance_ssd``;
# ``parallel.moe.routed_experts``).  The kernel slot says nothing about them unless
# someone overrides: ``True`` takes the kernel wherever it can run,
# ``False`` forbids it (the composed path, for a comparison) — the one
# ``False`` the canonical slot keeps.  The word reaches ``attend``
# through ``parallel.tensor.kernel_scope``, which the collective, GSPMD
# and pipeline lowerings open around the model they trace, the serving
# layout from the engine that builds it, and the routed layer through
# the engine's ``_ffn``.
OBSERVED_KERNELS = ("flash_attention", "delta_step", "grouped_matmul",
                    "retention_step", "ssd_step")

# Op-metadata marker prefix: `with jax.named_scope(kernel_marker(name))`
# around a pallas_call stamps every emitted op's `op_name` metadata, and
# the string survives XLA optimization (fusion keeps per-instruction
# metadata) — analysis/facts.py counts these per kernel.
_MARKER_PREFIX = "adtk_"


def kernel_marker(name: str) -> str:
    """The ``named_scope`` string an elected kernel's call site wears."""
    if name not in KERNEL_CHOICES:
        raise ValueError(f"unknown kernel {name!r}; expected one of "
                         f"{list(KERNEL_CHOICES)}")
    return _MARKER_PREFIX + name


def default_interpret() -> bool:
    """Pallas interpreter off-TPU (CPU goldens / simulated meshes);
    Mosaic compilation on real silicon."""
    import jax

    return jax.default_backend() != "tpu"


def __getattr__(name):
    # Lazy kernel re-exports: importing the registry (strategy/ir.py
    # does, at module import) must not pull jax.experimental.pallas.
    if name == "flash_prefill_attention_paged":
        from autodist_tpu.kernel.pallas.flash_prefill import \
            flash_prefill_attention_paged
        return flash_prefill_attention_paged
    if name == "quantized_ring_all_reduce":
        from autodist_tpu.kernel.pallas.quant_ring import \
            quantized_ring_all_reduce
        return quantized_ring_all_reduce
    if name == "collective_matmul_row_fused":
        from autodist_tpu.kernel.pallas.collective_matmul import \
            collective_matmul_row_fused
        return collective_matmul_row_fused
    if name == "quantized_ring_all_to_all":
        from autodist_tpu.kernel.pallas.a2a_ring import \
            quantized_ring_all_to_all
        return quantized_ring_all_to_all
    raise AttributeError(name)
