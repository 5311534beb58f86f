"""Analytic strategy cost model.

The reference's AutoSync simulator was a *stub* — an empty package plus
the dataset README describing per-(model, strategy, resource) runtime
records for training a learned cost model
(``autodist/simulator/dataset/README.md:1-94``).  This module supplies
the working equivalent analytically: per-variable communication volume,
collective-launch latency, and per-device memory for a candidate
strategy on a given TPU topology, using the per-generation hardware
constants in :mod:`autodist_tpu.resource`.

Costs are *relative* ranks, not wall-clock predictions: compute time is
strategy-invariant for the data-parallel family, so strategies are
ordered by communication time plus a memory-feasibility gate.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional

from autodist_tpu.capture import Trainable
from autodist_tpu.resource import ResourceSpec
from autodist_tpu.strategy.ir import Strategy

# Per-collective launch overhead (seconds).  ICI collectives are
# microsecond-scale to start; the exact constant only needs to penalize
# many-small-collective plans relative to bucketed ones.
COLLECTIVE_ALPHA = 5e-6

# Payload scale factors per compressor (grad bytes on the wire).
# Analytic defaults; :func:`load_calibration` / the
# ``tools/calibrate_compressors.py`` driver replace them with measured
# wall-clock ratios (int8_ring's p-1 sequential ppermute hops and
# PowerSGD's per-step Gram-Schmidt are NOT free — a byte count alone
# overstates both).
COMPRESSOR_FACTOR = {
    "none": 1.0,
    "fp16": 0.5, "bf16": 0.5,
    "fp16_ef": 0.5, "bf16_ef": 0.5,
    # int8_ef quantizes to int8 levels but its psum rides an fp16 wire;
    # int8_ring is the true-int8-wire ring.
    "int8_ef": 0.5,
    "int8_ring": 0.25,
    # (n + m)·r vs n·m bytes, ~2r/sqrt(total): a static stand-in for a
    # data-dependent ratio; at BERT-scale buckets it is ≲ 0.01.
    "powersgd": 0.02,
}

# Activation bytes per element on the wire/in HBM (bf16 activations).
_ACT_BYTES = 2.0

# The tied-table naming the pipeline vocab rules key on
# (parallel_builders.PIPELINE_VOCAB_RULES): used to identify the
# unembedding among replicated shared variables when no partitioner
# spec marks it.
_VOCAB_NAME_RE = re.compile(r"(^|/)embedding$")

# Link-pricing constants for the overlap-aware model (the pipeline/TP
# path): effective per-link bandwidth, per-hop launch latency, and the
# matmul efficiency that converts chunk FLOPs into the compute time a
# hop can hide behind.  Analytic defaults come from the chip table
# (resource.ChipSpec) / COLLECTIVE_ALPHA; a ``"link"`` section in
# calibration.json (or an explicit ``CostModel(link_profile=...)``)
# replaces them with measured values.  Keys: ``ici_gbps``,
# ``hop_alpha_s``, ``mxu_efficiency`` — and, for the cross-slice (DCN)
# level of the hierarchical network model, ``dcn_gbps`` /
# ``dcn_alpha_s`` (merged from calibration exactly like ``ici_gbps``;
# the drift report proposes both).
LINK_PROFILE: dict = {}

# Fraction of peak matmul throughput a pipeline-stage chunk sustains —
# only the *ratio* of chunk-compute to hop-transfer time matters for
# ranking overlapped vs blocking plans.
_DEFAULT_MXU_EFFICIENCY = 0.4

# Per-collective precision pricing (the Strategy IR policy, PR 8).
# Wire factors per boundary mechanism: a *summing* collective carries
# int8 levels on an fp16 wire (kernel/quantize.py), so int8 and bf16
# both halve psum bytes; a *gather* never sums and rides a TRUE s8
# wire — the full 4x.
PSUM_WIRE_FACTOR = {"fp32": 1.0, "bf16": 0.5, "int8": 0.5}
GATHER_WIRE_FACTOR = {"fp32": 1.0, "bf16": 0.5, "int8": 0.25}

# Quantize/dequantize compute per payload element (seconds) — the term
# byte counts miss: narrowing only wins when the bytes saved outweigh
# these passes.  Analytic defaults (a cast is one memory-bound pass;
# int8 adds the abs-max reduction and round/clip); a ``"quant"`` section
# in calibration.json (written by ``tools/calibrate_compressors.py``)
# replaces them with measured values, exactly like the ``"link"``
# constants.
QUANT_PROFILE: dict = {
    "bf16_s_per_elem": 2e-11,
    "int8_s_per_elem": 1e-10,
}

# Fused-kernel tier pricing (the Strategy IR ``kernel`` slot, PR 13) —
# analytic defaults; a ``"kernel"`` section in calibration.json
# (``tools/flash_crossover.py --prefill --write-calibration`` writes
# the prefill constants) replaces them like ``"link"`` and
# ``"quant"``:
#
# * ``quant_ring_wire_factor`` — the EQuARX ring's TRUE-s8 wire vs the
#   composed int8 psum's fp16-levels wire (0.25 vs PSUM_WIRE_FACTOR's
#   0.5): the ring halves the bytes again.
# * ``quant_ring_qdq_factor`` — the q/dq passes the per-hop fused
#   requantization costs relative to the composed sandwich's one
#   quantize + one dequantize (each hop re-quantizes, so ~2x at tp=2
#   and growing with hops; the fused VMEM pass keeps it near the byte
#   count rather than 2(n-1) full passes).
# * ``fused_hop_alpha_s`` — per-hop launch overhead of the fused
#   collective-matmul ring step (one kernel issues the hop's
#   accumulate+matmul, and on silicon its RDMA): the composed ring
#   pays the full ``hop_alpha_s`` per hop.
# * ``flash_decode_crossover_len`` / ``flash_decode_speedup`` /
#   ``flash_decode_short_penalty`` — the decode einsum-vs-flash
#   crossover: past the crossover length flash divides the attention
#   term by the measured speedup; below it the kernel's fixed overhead
#   *loses* to einsum by the penalty factor (the round-3 verdict's
#   measured shape), so the search elects flash exactly when the cache
#   length favors it.
KERNEL_PROFILE: dict = {
    "quant_ring_wire_factor": 0.25,
    "quant_ring_qdq_factor": 2.0,
    "fused_hop_alpha_s": 1e-6,
    "flash_decode_crossover_len": 1024,
    "flash_decode_speedup": 1.6,
    "flash_decode_short_penalty": 0.8,
    # Paged-KV table indirection: the attention term's multiplier under
    # kv_layout="paged" (block-table gathers / per-block DMA setup vs
    # the dense contiguous lane).  Strictly > 1 so dense wins whenever
    # the request-length distribution gives paged no capacity edge —
    # the both-ways election contract.
    "paged_attention_overhead": 1.05,
    # Throughput-ladder constants (PR 16), calibratable like the rest:
    #
    # * ``flash_prefill_crossover_chunk`` / ``flash_prefill_speedup`` /
    #   ``flash_prefill_short_penalty`` — the chunked-prefill
    #   einsum-vs-flash crossover over CHUNK size (``tools/
    #   flash_crossover.py --prefill`` measures it): wide chunks
    #   amortize the kernel's scalar-prefetch setup, narrow ones lose
    #   to the composed gather path.
    # * ``prefix_caching_overhead`` — hash/admission bookkeeping plus
    #   the occasional copy-on-write, as an attention-term multiplier.
    #   Strictly > 1 so a traffic mix with NO shared prefixes elects
    #   plain paged — the hit rate must pay for the knob both ways.
    # * ``spec_draft_flops_frac`` — draft-model cost per proposed token
    #   relative to a target decode step (a ~7x-smaller draft).
    # * ``spec_marginal_token_cost`` — the verify window's marginal
    #   cost per extra token relative to a full decode step: the k+1
    #   tokens share one weights read and one dispatch, so each extra
    #   token costs well under a step (the whole point of verifying a
    #   window at once).
    # * ``spec_acceptance_default`` — the acceptance rate assumed when
    #   the caller has not measured one.
    "flash_prefill_crossover_chunk": 128,
    "flash_prefill_speedup": 1.5,
    "flash_prefill_short_penalty": 0.85,
    "prefix_caching_overhead": 1.02,
    "spec_draft_flops_frac": 0.15,
    "spec_marginal_token_cost": 0.35,
    "spec_acceptance_default": 0.7,
    # MoE dispatch/combine ring (``a2a_ring``, the quant_ring
    # generalized from reduce to permute).  Unlike the reduce ring, the
    # composed int8 all_to_all ALREADY ships true s8 (a permute never
    # sums, so there is no fp16-levels headroom wire to beat) — the
    # analytic wire factor therefore matches GATHER_WIRE_FACTOR's int8
    # 0.25 and the election crossover lives in the q/dq term: the fused
    # hop quantizes/dequantizes in VMEM (``a2a_ring_qdq_factor`` < 1 vs
    # the composed sandwich's HBM-shaped converts) but pays 2(n-1) hop
    # launches per dispatch+combine pair where the monolithic collective
    # pays 2 — so the ring wins exactly when the payload is large enough
    # that the q/dq saving clears the extra alphas (analytic: neither
    # has been measured on silicon).
    "a2a_ring_wire_factor": 0.25,
    "a2a_ring_qdq_factor": 0.5,
}

# The grad slot's realization: which EF compressor a bf16/int8 gradient
# policy elects (mirrors lower_pipeline_ir / build_replicated_spmd).
_GRAD_PRECISION_COMPRESSOR = {"bf16": "bf16_ef", "int8": "int8_ef"}


def _qdq_s_per_elem(profile: dict, precision: str) -> float:
    if precision == "fp32":
        return 0.0
    return float(profile.get(f"{precision}_s_per_elem",
                             QUANT_PROFILE.get(f"{precision}_s_per_elem",
                                               0.0)))


def load_calibration(path: Optional[str] = None) -> dict:
    """Merge measured compressor factors into :data:`COMPRESSOR_FACTOR`.

    ``tools/calibrate_compressors.py`` times each compressor's allreduce
    against the uncompressed one on the real chip and writes
    ``{"compressor_factor": {name: measured_ratio}, ...}``; loading it
    turns the cost model's byte-count guesses into wall-clock ratios.
    An optional ``"link"`` section (``ici_gbps`` / ``hop_alpha_s`` /
    ``mxu_efficiency``) merges into :data:`LINK_PROFILE` the same way —
    the constants the overlap-aware pipeline pricing uses in place of
    the chip-table defaults.  Default path: ``calibration.json`` at the
    repo root, then the ``AUTODIST_TPU_CALIBRATION`` env var.  Returns
    the compressor factors applied (empty when no file exists).
    """
    import json
    import os

    candidates = [path] if path else [
        os.environ.get("AUTODIST_TPU_CALIBRATION", ""),
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), "calibration.json"),
    ]
    for p in candidates:
        if p and os.path.exists(p):
            with open(p) as f:
                data = json.load(f)
            meta = data.get("meta")
            backend = meta.get("backend") if isinstance(meta, dict) else None
            if path is None and backend == "cpu":
                # A dev-smoke artifact (tools/calibrate_compressors.py on
                # a CPU mesh) measures compute overhead with no real wire
                # and would silently skew accelerator planning; auto-load
                # skips it.  An explicit ``path`` argument overrides.
                from autodist_tpu.utils import logging
                logging.warning(
                    "ignoring CPU-provenance calibration file %s "
                    "(pass the path explicitly to force)", p)
                continue
            factors = dict(data.get("compressor_factor", {}))
            COMPRESSOR_FACTOR.update(factors)
            LINK_PROFILE.update(dict(data.get("link", {})))
            # Measured quantize/dequantize per-element costs (the
            # ``"quant"`` section ``tools/calibrate_compressors.py``
            # emits) replace the analytic q/dq defaults the same way.
            QUANT_PROFILE.update(dict(data.get("quant", {})))
            # Measured fused-kernel constants (``tools/flash_crossover
            # .py --prefill --write-calibration``) replace the kernel
            # tier's analytic defaults the same way.
            KERNEL_PROFILE.update(dict(data.get("kernel", {})))
            return factors
    return {}


_calibration_loaded = False


def _ensure_calibration():
    global _calibration_loaded
    if not _calibration_loaded:
        _calibration_loaded = True
        applied = load_calibration()
        if applied:
            from autodist_tpu.utils import logging
            logging.info("cost model using measured compressor factors: %s",
                         applied)


class SpecMeshMismatch(ValueError):
    """A GSPMD sharding spec names a mesh axis the topology lacks —
    the candidate is invalid for this resource spec (AutoStrategy skips
    it), as opposed to a genuine cost-model error."""


@dataclasses.dataclass
class StrategyCost:
    """Breakdown for one (trainable, strategy, topology) triple."""

    comm_bytes: float          # total collective payload per step
    comm_time_s: float         # bandwidth term + per-collective latency
    num_collectives: int
    mem_bytes_per_device: float
    feasible: bool             # fits in HBM (with headroom)
    # Exposed (un-hidden) time of latency-hiding decompositions, already
    # included in comm_time_s; broken out so the telemetry drift report
    # can show comm vs exposed-overlap per term.
    overlap_time_s: float = 0.0
    # Peak loss-head logits buffer (pipeline lowering, priced only with
    # a tokens hint), already included in mem_bytes_per_device; broken
    # out because it is the term vocab parallelism divides by tp — the
    # drift report joins it against measured HBM and telemetry gauges it.
    peak_logits_bytes: float = 0.0
    # Predicted per-device parameter-storage and gradient bytes after
    # sharding (parallel lowerings), already included in
    # mem_bytes_per_device; broken out like peak_logits_bytes because
    # they are the terms the ZeRO stages divide — stage 2 shards the
    # gradient term by the data-replica count, stage 3 the parameter
    # term too — so the drift report can attribute an HBM delta between
    # stages to the right term.
    param_shard_bytes: float = 0.0
    grad_shard_bytes: float = 0.0
    # Per-collective precision policy terms: bytes the narrowed wire
    # saves vs the same plan at fp32 (already reflected in comm_bytes/
    # comm_time_s — broken out so the drift report can show the
    # predicted bytes-on-wire delta), and the quantize/dequantize
    # compute charged against it (also already inside comm_time_s): a
    # narrowed candidate outranks fp32 exactly when saved wire time
    # outweighs this term.
    wire_bytes_saved: float = 0.0
    quant_dq_time_s: float = 0.0
    # Per-level breakdown of the hierarchical network model: the bytes
    # and time of the cross-slice (DCN) exchanges, already included in
    # comm_bytes / comm_time_s.  A collective spanning the dcn axis
    # decomposes into intra-slice reduce + cross-slice exchange +
    # intra-slice broadcast (arxiv 2110.10548); this is the cross-slice
    # term, priced at the dcn_gbps/dcn_alpha_s constants — broken out
    # so the drift report can fit dcn_gbps independently of ici_gbps
    # and the search report can show per-level comm per candidate.
    dcn_bytes: float = 0.0
    dcn_time_s: float = 0.0
    # Expert-parallel all_to_all term (MoE dispatch + combine, forward
    # and backward), already included in comm_bytes / comm_time_s (or
    # the dcn terms when the expert axis spans slices) — broken out so
    # the drift report can join the predicted dispatch/combine wire
    # against the measured step and the search report can show the
    # placement trade (within-slice ICI vs across-DCN) per candidate.
    a2a_bytes: float = 0.0
    a2a_time_s: float = 0.0

    @property
    def score(self) -> float:
        """Lower is better; infeasible plans rank last."""
        return self.comm_time_s if self.feasible else math.inf


@dataclasses.dataclass
class DecodeCost:
    """Per-token decode latency breakdown for one serving config — the
    cost model's second objective (latency under load, not training
    step time).  ``token_time_s = compute + comm``: raising the tp
    degree divides the per-device matmul work but adds the per-layer
    Megatron boundary all-reduces, so tp=2 ranks above tp=1 exactly
    when the per-token comm cost is under the compute win."""

    token_time_s: float        # comm + compute, per decoded token
    comm_time_s: float         # model-axis boundary collectives
    compute_time_s: float      # per-device matmul passes
    kv_bytes_per_device: float     # the TP-sharded cache's footprint
    mem_bytes_per_device: float    # params (sharded) + KV cache
    feasible: bool
    tensor_parallel: int = 1
    vocab_parallel: bool = False
    # Attention-over-cache share of compute_time_s (already included):
    # the term the flash_decode kernel divides by its calibrated
    # speedup past the crossover length — broken out so the election
    # report can show why flash won (or lost) at this cache length.
    attn_time_s: float = 0.0
    kernel: tuple = ()
    # The capacity side of the serving objective (PR 14): the KV-cache
    # layout this config serves with, and the expected number of
    # concurrent requests the post-params HBM supports under the
    # request-length distribution — dense reserves a full max_len lane
    # per request; paged reserves only the mean length rounded up to a
    # block, so length variance below max_len multiplies capacity.
    kv_layout: str = "dense"
    request_capacity: float = 0.0
    # The fleet shape (PR 15): dp replicas of the tp group behind one
    # router.  Replicas multiply capacity without touching per-token
    # latency; a fleet spanning slices pays the router's cross-slice
    # dispatch hop (priced at DCN constants, amortized per token) —
    # replicas ride DCN, tp never does (the serving ADT060 analog,
    # rejected at pricing time).
    replicas: int = 1
    dispatch_time_s: float = 0.0
    # The throughput ladder (PR 16): which rungs this config runs, and
    # the traffic facts they were priced under.  ``spec_acceptance`` is
    # the acceptance rate the speculative term used (0 when off);
    # ``prefix_hit_rate`` the shared-prefix block fraction the capacity
    # term used (0 when off).
    prefill_chunk: Optional[int] = None
    prefix_caching: bool = False
    prefix_hit_rate: float = 0.0
    speculative: Optional[int] = None
    spec_acceptance: float = 0.0
    # Disaggregated serving (PR 17): the prefill/decode pool split and
    # its per-request stage times.  ``prefill_time_s`` is one request's
    # prompt pass on one prefill replica; ``decode_time_s`` its decode
    # tail on one decode replica; ``handoff_time_s`` the KV prefix
    # transfer between them (ICI when the pools share a slice, DCN when
    # the split spans slices) — the term that makes a split with too
    # little decode capacity pay for every handoff it absorbs.
    prefill_replicas: int = 0
    decode_replicas: int = 0
    prefill_time_s: float = 0.0
    decode_time_s: float = 0.0
    handoff_time_s: float = 0.0
    # A mixed stack's linear layers: the recurrent state read and
    # written a token, at the HBM rate (part of ``compute_time_s``).
    state_time_s: float = 0.0

    @property
    def score(self) -> float:
        """Lower is better; infeasible configs rank last."""
        return self.token_time_s if self.feasible else math.inf

    @property
    def serve_score(self) -> float:
        """The capacity-aware objective: per-token latency divided by
        the concurrent requests the HBM carries — ~1/aggregate
        throughput under load.  Paged outranks dense on it exactly when
        the capacity multiplier beats the table-indirection overhead
        (i.e. when length variance makes dense reservation wasteful);
        at mean length == max_len the capacities tie and the overhead
        makes dense win — pinned both ways."""
        if not self.feasible or self.request_capacity <= 0:
            return math.inf
        return self.token_time_s / self.request_capacity

    @property
    def fleet_score(self) -> float:
        """The fleet objective: per-token latency (+ the amortized
        cross-slice dispatch hop) over the requests the WHOLE fleet
        carries (``replicas × request_capacity``) — ~1/aggregate fleet
        throughput for the traffic mix.  Elects the
        (replicas × tp × kv_layout) shape: replicas multiply the
        denominator for free until the device budget binds, tp trades
        per-token comm against the compute win within a slice, and the
        kv layout moves ``request_capacity`` exactly as in
        :attr:`serve_score`."""
        if not self.feasible or self.request_capacity <= 0:
            return math.inf
        return (self.token_time_s + self.dispatch_time_s) \
            / (max(self.replicas, 1) * self.request_capacity)

    @property
    def disagg_score(self) -> float:
        """The disaggregation objective: a request pipeline's
        bottleneck stage time — prefill work spread over the prefill
        pool vs (handoff + decode) work spread over the decode pool.
        Lower is better (~1/aggregate request throughput at the
        bottleneck).  A prefill-bound mix (long prompts, short decode
        tails) elects a split with more prefill replicas; a
        decode-bound mix the reverse — and every handoff the decode
        pool absorbs is charged to ITS stage, so starving decode never
        looks free (both directions pinned)."""
        if not self.feasible or self.prefill_replicas < 1 \
                or self.decode_replicas < 1:
            return math.inf
        prefill = self.prefill_time_s / self.prefill_replicas
        decode = (self.decode_time_s + self.handoff_time_s) \
            / self.decode_replicas
        return max(prefill, decode)


class CostModel:
    """Scores strategies against a resource spec's topology constants."""

    def __init__(self, resource_spec: ResourceSpec, *,
                 sparsity_fraction: float = 0.05,
                 opt_state_multiplier: float = 2.0,
                 hbm_headroom: float = 0.6,
                 tokens_per_step: Optional[int] = None,
                 act_bytes_per_token: Optional[float] = None,
                 link_profile: Optional[dict] = None,
                 quant_profile: Optional[dict] = None,
                 kernel_profile: Optional[dict] = None):
        """``sparsity_fraction``: expected fraction of embedding rows
        touched per step (drives the sparse gather/scatter volume).
        ``opt_state_multiplier``: optimizer slots per parameter byte
        (2.0 = adam m+v).  ``hbm_headroom``: fraction of HBM the model
        state may occupy (the rest is activations/workspace).
        ``tokens_per_step`` / ``act_bytes_per_token``: activation-shape
        hints (override the trainable's own) enabling activation-
        collective and activation-memory pricing — see
        :class:`~autodist_tpu.capture.Trainable`.
        ``link_profile``: per-link constants for the overlap-aware
        pricing (keys ``ici_gbps``/``hop_alpha_s``/``mxu_efficiency``);
        overrides the calibration-file :data:`LINK_PROFILE`, which
        overrides the chip-table defaults.
        ``quant_profile``: quantize/dequantize per-element costs for the
        precision-policy pricing (keys ``bf16_s_per_elem`` /
        ``int8_s_per_elem``); same override chain as ``link_profile``
        against :data:`QUANT_PROFILE`.
        ``kernel_profile``: fused-kernel tier constants (see
        :data:`KERNEL_PROFILE`); same override chain."""
        _ensure_calibration()
        self.spec = resource_spec
        self.chip = resource_spec.chip
        self.sparsity_fraction = sparsity_fraction
        self.opt_state_multiplier = opt_state_multiplier
        self.hbm_headroom = hbm_headroom
        self.tokens_per_step = tokens_per_step
        self.act_bytes_per_token = act_bytes_per_token
        self.link_profile = dict(LINK_PROFILE)
        if link_profile:
            self.link_profile.update(link_profile)
        self.quant_profile = dict(QUANT_PROFILE)
        if quant_profile:
            self.quant_profile.update(quant_profile)
        self.kernel_profile = dict(KERNEL_PROFILE)
        if kernel_profile:
            self.kernel_profile.update(kernel_profile)

    # ------------------------------------------------------------------ #
    def with_spec(self, resource_spec: ResourceSpec) -> "CostModel":
        """The same pricing constants bound to a different resource
        spec — how the topology-aware search prices each candidate
        against its *own* mesh factorization (the mesh is read from
        ``self.spec``, so pricing a re-factored candidate with the
        original model would silently ignore its pp/tp/dcn degrees)."""
        return CostModel(resource_spec,
                         sparsity_fraction=self.sparsity_fraction,
                         opt_state_multiplier=self.opt_state_multiplier,
                         hbm_headroom=self.hbm_headroom,
                         tokens_per_step=self.tokens_per_step,
                         act_bytes_per_token=self.act_bytes_per_token,
                         link_profile=self.link_profile,
                         quant_profile=self.quant_profile,
                         kernel_profile=self.kernel_profile)

    def _dcn_link(self) -> tuple[float, float]:
        """(bytes/s, launch alpha) of the cross-slice DCN level —
        calibrated ``"link"`` ``dcn_*`` constants over the chip-table
        defaults, the same override chain as ``ici_gbps``."""
        bw = float(self.link_profile.get(
            "dcn_gbps", getattr(self.chip, "dcn_gbps", 5.0))) * 1e9
        alpha = float(self.link_profile.get(
            "dcn_alpha_s", getattr(self.chip, "dcn_alpha_s", 1e-4)))
        return bw, alpha

    def _dcn_degree(self, mesh: dict) -> int:
        """Slice count the replica sync crosses: the mesh's ``dcn``
        axis — or, when an explicit mesh omits it on a declared
        multi-slice topology, ``num_slices`` (the data axis still
        physically crosses slices whether or not the user named the
        level; pricing it flat would be exactly the mispricing the
        hierarchical model exists to fix)."""
        from autodist_tpu import const

        n_dcn = max(int(mesh.get(const.DCN_AXIS, 1) or 1), 1)
        if n_dcn == 1:
            n_dcn = max(int(getattr(self.spec, "num_slices", 1) or 1), 1)
        return n_dcn

    @staticmethod
    def _split_ring(n_sync: int, n_dcn: int) -> tuple[float, float]:
        """Hierarchical ring factors for a replica-sync group of
        ``n_sync`` members of which ``n_dcn`` cross slices: intra-slice
        reduce-scatter + broadcast at ICI rates plus a cross-slice
        exchange of the intra-slice shard at DCN rates (the two-level
        reduction shape of arxiv 2110.10548).  Returns ``(ici_factor,
        dcn_factor)`` — multiply each by the payload bytes and price at
        its level's bandwidth.  Pure-ICI groups (``n_dcn == 1``) keep
        today's exact single-level factor, so single-slice pricing is
        byte-identical to the flat model."""
        def ring(k: int) -> float:
            return 2.0 * (k - 1) / k if k > 1 else 0.0

        if n_dcn <= 1 or n_sync % n_dcn:
            return ring(n_sync), 0.0
        g = n_sync // n_dcn
        return ring(g), ring(n_dcn) / max(g, 1)

    def _hints(self, trainable) -> tuple[Optional[int], Optional[float]]:
        tokens = self.tokens_per_step if self.tokens_per_step is not None \
            else getattr(trainable, "tokens_per_step", None)
        act = self.act_bytes_per_token if self.act_bytes_per_token is not None \
            else getattr(trainable, "act_bytes_per_token", None)
        return tokens, act

    @staticmethod
    def _hidden_dim(trainable) -> int:
        """Activation width estimate: the largest 'matmul contraction'
        dim, i.e. max over rank>=2 variables of their smallest dim
        (embedding [V, H] and square projections [H, H] both yield H)."""
        dims = [min(v.shape) for v in trainable.var_infos()
                if len(v.shape) >= 2]
        return max(dims) if dims else 1

    @staticmethod
    def _gspmd_shards(node, mesh) -> tuple[int, bool]:
        """(device count the node's spec shards one variable over, whether
        the data axis is among its sharding axes); raises
        :class:`SpecMeshMismatch` when the spec names an axis the
        topology lacks."""
        from autodist_tpu import const

        part = node.partitioner
        shards, uses_data = 1, False
        spec = part.spec if part is not None and part.spec is not None \
            else None
        if spec is None:
            if part is not None and part.num_shards > 1:
                shards = part.num_shards
            return shards, uses_data
        for axis in spec:
            for a in (axis if isinstance(axis, (list, tuple)) else [axis]):
                if a is None:
                    continue
                if a not in mesh:
                    raise SpecMeshMismatch(
                        f"{node.var_name}: spec names mesh axis {a!r} "
                        f"absent from topology {mesh}")
                shards *= mesh[a]
                uses_data |= a == const.DATA_AXIS
        return shards, uses_data

    def _gspmd_cost(self, trainable, strategy) -> StrategyCost:
        """Pricing for gspmd-lowered strategies.

        * data-axis-sharded (FSDP layout): state at 1/shards; per step the
          grads reduce-scatter and the params all-gather over the data
          axis — ring-equivalent *full* tensor volume, same as the
          collective path's sharded branch.
        * model-axis-sharded (TP): each device permanently owns its
          slice; only the slice's gradient syncs over the data axis.
          With a ``tokens_per_step`` hint, activation collectives on the
          model axis are priced Megatron-style: each *row-parallel*
          variable (dim 0 sharded on the model axis, e.g. the out-proj /
          mlp-down matmul) implies one fwd activation allreduce of
          ``tokens x out_features`` over its TP group, mirrored in the
          backward at its column-parallel partner — 2x the fwd volume,
          charged on the row var to avoid double counting.  Without the
          hint they appear in the per-collective latency term only.
        * replicated: the DP grad allreduce.
        """
        from autodist_tpu import const

        mesh = self.spec.resolved_mesh_shape()
        n = max(strategy.graph_config.replicas, 1)
        infos = {v.name: v for v in trainable.var_infos()}
        # The replica group spans data x dcn; dcn-crossing sync
        # decomposes per level (intra-slice at ICI + cross-slice shard
        # exchange at DCN) instead of pricing everything at ici_gbps.
        ring, dcn_factor = self._split_ring(n, self._dcn_degree(mesh))
        bw_dcn, dcn_alpha = self._dcn_link()
        dcn_bytes = dcn_time = 0.0
        dcn_colls = 0
        total_devices = 1
        for v in mesh.values():
            total_devices *= v
        tokens, act_hint = self._hints(trainable)
        m = mesh.get(const.MODEL_AXIS, 1)
        ring_m = 2.0 * (m - 1) / m if m > 1 else 0.0
        tokens_per_group = (tokens / n) if tokens else 0.0
        comm_bytes = mem_bytes = 0.0
        num_collectives = 0
        # Iterate var_infos: variables a hand-edited strategy omitted a
        # node config for still train replicated — price them too.
        nodes_by_name = {nc.var_name: nc for nc in strategy.node_configs}
        _no_node = type("_NoNode", (), {"partitioner": None,
                                        "synchronizer": None})()
        for info in infos.values():
            node = nodes_by_name.get(info.name, _no_node)
            bytes_ = float(info.byte_size)
            shards, uses_data = self._gspmd_shards(node, mesh)
            is_ps = getattr(node.synchronizer, "kind", "") == "ps"
            if shards > 1:
                # PS on a TP-sharded var: kernel/gspmd.py additionally
                # shards the state's dim 0 over the data axes when it
                # divides — a further 1/n on the opt term.
                opt_div = shards
                if is_ps and n > 1 and info.shape \
                        and info.shape[0] % (shards * n) == 0:
                    opt_div = shards * n
                mem_bytes += bytes_ * 2.0 / shards \
                    + bytes_ * self.opt_state_multiplier / opt_div
                payload = bytes_ if uses_data else bytes_ / shards
                comm_bytes += ring * payload
                num_collectives += 2
                if dcn_factor:
                    dcn_bytes += dcn_factor * payload
                    dcn_colls += 2
                # Row-parallel on the model axis: fwd+bwd activation
                # allreduce of tokens x shape[1] over the TP group.
                part = node.partitioner
                spec0 = part.spec[0] if part is not None \
                    and part.spec else None
                row_parallel = (
                    ring_m > 0.0 and tokens and len(info.shape) >= 2
                    and (const.MODEL_AXIS == spec0
                         or (isinstance(spec0, (list, tuple))
                             and const.MODEL_AXIS in spec0)))
                if row_parallel:
                    # Output width = the last (non-contracted) dim: H for
                    # out-proj [heads, head_dim, H], wo [mlp, H], and the
                    # vocab-sharded embedding [V, H] (partial-sum lookup).
                    comm_bytes += 2.0 * ring_m * tokens_per_group \
                        * info.shape[-1] * _ACT_BYTES
                    num_collectives += 2
            else:
                # PS(sync=True) under gspmd = GSPMD ZeRO-1 (opt state's
                # leading dim shards over the data axes, kernel/gspmd.py);
                # reduce-scatter + all-gather replace the allreduce at
                # ring-equivalent volume.
                opt_div = n if (is_ps and n > 1) else 1
                mem_bytes += bytes_ * 2.0 \
                    + bytes_ * self.opt_state_multiplier / opt_div
                comm_bytes += ring * bytes_
                num_collectives += 2 if opt_div > 1 else 1
                if dcn_factor:
                    dcn_bytes += dcn_factor * bytes_
                    dcn_colls += 2 if opt_div > 1 else 1
        if tokens and act_hint:
            # Activations divide by the number of batch shards (the data
            # axis), not all devices: a TP group processes the same
            # tokens on every member (the residual stream is unsharded —
            # conservative; some TP intermediates do shard).
            mem_bytes += act_hint * tokens / n
        bw = self.chip.ici_gbps * 1e9
        comm_time = comm_bytes / bw \
            + COLLECTIVE_ALPHA * num_collectives * (1 if total_devices > 1
                                                    else 0)
        if dcn_bytes:
            dcn_time = dcn_bytes / bw_dcn + dcn_alpha * dcn_colls
            comm_time += dcn_time
        hbm = self.chip.hbm_gb * 1e9 * self.hbm_headroom
        return StrategyCost(comm_bytes=comm_bytes + dcn_bytes,
                            comm_time_s=comm_time,
                            num_collectives=num_collectives + dcn_colls,
                            mem_bytes_per_device=mem_bytes,
                            feasible=mem_bytes <= hbm,
                            dcn_bytes=dcn_bytes, dcn_time_s=dcn_time)

    def _parallel_cost(self, trainable, strategy) -> StrategyCost:
        """Pricing for the sequence / pipeline / expert lowerings.

        Uses the activation hints where collective volume is activation-
        shaped (ring-attention k/v rotation, pipeline activation hops,
        MoE all_to_all); without hints those appear only in the latency
        term — same documented degradation as TP.
        """
        from autodist_tpu import const

        mesh = self.spec.resolved_mesh_shape()
        kind = strategy.graph_config.lowering
        tokens, act_hint = self._hints(trainable)
        hidden = self._hidden_dim(trainable)
        n_data = mesh.get(const.DATA_AXIS, 1) * mesh.get(const.DCN_AXIS, 1)
        total_devices = 1
        for v in mesh.values():
            total_devices *= v
        infos = list(trainable.var_infos())
        opt_mult = self.opt_state_multiplier
        comm = 0.0
        colls = 0
        mem = 0.0
        tokens_per_dev = (tokens / total_devices) if tokens else 0.0
        # Link constants for the overlap-aware pricing (and this branch's
        # final bytes→time conversion, so overlapped and blocking
        # variants are ranked against ONE set of constants): calibrated
        # values beat the chip table.
        bw_link = float(self.link_profile.get(
            "ici_gbps", self.chip.ici_gbps)) * 1e9
        hop_alpha = float(self.link_profile.get(
            "hop_alpha_s", COLLECTIVE_ALPHA))
        mxu_eff = float(self.link_profile.get(
            "mxu_efficiency", _DEFAULT_MXU_EFFICIENCY))
        flops_rate = self.chip.peak_bf16_tflops * 1e12 * mxu_eff
        # Hierarchical network model: any sync group spanning the dcn
        # axis decomposes into an intra-slice part (priced through the
        # ICI `comm` pool below) and a cross-slice shard exchange priced
        # at the DCN constants here — never at ici_gbps.
        n_dcn = self._dcn_degree(mesh)
        bw_dcn, dcn_alpha = self._dcn_link()
        dcn_b = 0.0      # cross-slice wire bytes
        dcn_t = 0.0      # cross-slice time, launch alphas included
        dcn_colls = 0
        # Overlapped collectives are priced in *seconds* directly (their
        # per-hop alphas included), with their wire bytes and launch
        # counts reported but not re-charged through the bytes/bw + alpha
        # terms below.
        overlap_s = 0.0
        hidden_bytes = 0.0
        extra_colls = 0
        peak_logits = 0.0
        # Expert dispatch/combine breakout (bytes ride the comm or dcn
        # pools above; the time share is re-derived for the report).
        a2a_b = 0.0
        a2a_t = 0.0

        # Per-collective precision policy (PR 8): wire factors shrink
        # each policied boundary's bytes; the q/dq compute term charges
        # the quantize/dequantize passes against the saving — a narrowed
        # plan outranks fp32 exactly when the saved wire time exceeds it.
        from autodist_tpu.strategy.ir import (normalize_kernel,
                                              normalize_precision)
        policy = normalize_precision(strategy.graph_config.precision)
        # Fused-kernel tier (PR 13): the quant_ring kernel trades the
        # composed int8 psum's fp16-levels wire for TRUE s8 at the cost
        # of per-hop requantization; the fused collective-matmul ring
        # shrinks the per-hop launch overhead.  Priced from the
        # calibratable KERNEL_PROFILE so the search elects each kernel
        # exactly when its crossover favors it.
        kern_cfg = normalize_kernel(
            getattr(strategy.graph_config, "kernel", None))
        ring_kernel = "quant_ring" in kern_cfg
        fused_mm = "collective_matmul" in kern_cfg
        kp = self.kernel_profile
        tp_prec = policy.get("tp_psum", "fp32")
        stats_prec = policy.get("vocab_stats", "fp32")
        z3_prec = policy.get("zero3_gather", "fp32")
        grad_prec = policy.get("grad", "fp32")
        qdq_s = 0.0
        saved_bytes = 0.0

        def qdq(elems: float, prec: str) -> float:
            return elems * _qdq_s_per_elem(self.quant_profile, prec)

        def ring(k: int) -> float:
            return 2.0 * (k - 1) / k if k > 1 else 0.0

        def split_ring(n_sync: int) -> tuple[float, float]:
            """(ici factor, dcn factor) of a replica sync group — see
            :meth:`_split_ring`; the dcn factor's bytes are priced at
            the DCN constants via :func:`dcn_sync` below."""
            return self._split_ring(n_sync, n_dcn)

        def dcn_sync(node, full_bytes: float, launches: int = 1):
            """One grad-sync boundary's cross-slice exchange: wire
            bytes after the node's compressor/grad-policy factor,
            priced at DCN bandwidth plus launch alphas."""
            nonlocal dcn_b, dcn_t, dcn_colls
            b = grad_bytes(node, full_bytes)
            dcn_b += b
            dcn_t += b / bw_dcn + dcn_alpha * launches
            dcn_colls += launches

        # Iterate var_infos (not node_configs): a hand-edited strategy
        # omitting node configs for some variables still trains them
        # (the lowerings default missing nodes to plain AllReduce), so
        # the pricing must cover every variable.
        nodes_by_name = {nc.var_name: nc for nc in strategy.node_configs}

        def node_factor(node) -> float:
            """Compressor wire factor (AllReduce nodes only; PS reduces
            at full precision).  A non-fp32 ``grad`` precision slot
            elects the matching EF compressor on every AllReduce node
            without an explicit one — exactly what the lowerings do."""
            sync = getattr(node, "synchronizer", None)
            if sync is None or getattr(sync, "kind", "allreduce") == "ps":
                return 1.0
            comp = (getattr(sync, "compressor", "none") or "none") \
                .partition(":")[0]
            if comp == "none" and grad_prec != "fp32":
                comp = _GRAD_PRECISION_COMPRESSOR[grad_prec]
            return COMPRESSOR_FACTOR.get(comp, 1.0)

        def grad_bytes(node, full_bytes: float) -> float:
            """Grad-sync bytes after the compressor/grad-policy factor,
            recording the policy's saving (not an explicit compressor's
            — that narrowing predates the policy and has no fp32
            sibling to diff against)."""
            nonlocal saved_bytes
            scaled = full_bytes * node_factor(node)
            sync = getattr(node, "synchronizer", None)
            if (grad_prec != "fp32" and sync is not None
                    and getattr(sync, "kind", "allreduce") != "ps"
                    and (getattr(sync, "compressor", "none") or "none")
                    == "none"):
                saved_bytes += full_bytes - scaled
            return scaled

        def node_is_ps(node) -> bool:
            return getattr(getattr(node, "synchronizer", None),
                           "kind", "") == "ps"

        def zero_divisors(node, group: int):
            """(stage, param_div, grad_div, opt_div) of a PS node over a
            ``group``-device replica set: stage 1 shards optimizer state,
            stage 2 additionally accounts the gradients sharded (same
            reduce-scatter program), stage 3 stores the parameters
            sharded too (all-gathered on demand per layer)."""
            if not node_is_ps(node) or group <= 1:
                return 0, 1, 1, 1
            stage = int(getattr(getattr(node, "synchronizer", None),
                                "zero_stage", 1) or 1)
            return (stage, group if stage >= 3 else 1,
                    group if stage >= 2 else 1, group)

        accum = max(int(strategy.graph_config.accum_steps or 1), 1)
        param_b = grad_b = 0.0   # per-device param/grad bytes (sharded)

        if kind == "sequence":
            S = mesh.get(const.SEQ_AXIS, 1)
            n_sync = n_data * S
            # params replicated; per-var sync over data x seq.  PS ->
            # ZeRO (parallel/_spmd.py): same ring-equivalent volume, opt
            # state at 1/n_sync (stage 2 accounts grads sharded, stage 3
            # stores params sharded); compressors scale the wire bytes.
            for info in infos:
                node = nodes_by_name.get(info.name)
                bytes_ = float(info.byte_size)
                stage, p_div, g_div, opt_div = zero_divisors(node, n_sync)
                param_b += bytes_ / p_div
                grad_b += bytes_ / g_div
                mem += bytes_ / p_div + bytes_ / g_div \
                    + bytes_ * opt_mult / opt_div
                f_ici, f_dcn = split_ring(n_sync)
                mult = accum if stage >= 3 else 1
                comm += grad_bytes(node, mult * f_ici * bytes_)
                if f_dcn:
                    dcn_sync(node, mult * f_dcn * bytes_,
                             2 * accum if stage >= 3
                             else 2 if opt_div > 1 else 1)
                colls += (2 * accum if stage >= 3
                          else 2 if opt_div > 1 else 1)
            if tokens:
                # ring attention: each device rotates its local k/v
                # (2 tensors of tokens_local x hidden) S-1 hops forward,
                # mirrored in the backward.
                comm += 2.0 * 2.0 * tokens_per_dev * hidden * _ACT_BYTES \
                    * (S - 1)
                colls += 2 * max(S - 1, 0)
            if tokens and act_hint:
                mem += act_hint * tokens_per_dev  # seq divides activations
        elif kind == "pipeline":
            S = mesh.get(const.PIPE_AXIS, 1)
            tp = mesh.get(const.MODEL_AXIS, 1)
            M = max(int(strategy.graph_config.parallel.get(
                "num_microbatches", 1)), 1)
            V = max(int(strategy.graph_config.parallel.get(
                "virtual_stages", 1)), 1)
            # Mode resolution mirrors lower_pipeline_ir exactly (graph
            # knob wins, per-variable fields fill in when it's unset,
            # aliases canonicalized) — the price must describe the
            # program that would actually be built.
            from autodist_tpu.parallel.tensor import normalize_comm_overlap
            overlap_cfg = normalize_comm_overlap(
                strategy.graph_config.parallel.get("comm_overlap"))
            tokens_local = tokens / max(n_data, 1) if tokens else 0.0
            emb_var = None      # ((priority, bytes), V, H, vocab shards)
            # V chunks of C = S*V total live per device -> stage
            # params/opt at 1/S, grads sync over the data axis; shared
            # (embedding/unembedding) vars replicate and sync over
            # pipe x data.  PS -> ZeRO-1: stage state at 1/(S*n_data),
            # shared state at 1/(S*n_data) too (pipe x data joint shard).
            # Tensor parallelism inside stages (dp×pp×tp): model-axis
            # entries in a stage var's spec further divide its state by
            # tp; each *row*-parallel var (model on the first per-stage
            # dim: the attention out-proj, mlp wo) adds the Megatron
            # activation all-reduce over the tp group per chunk
            # execution, fwd + bwd.
            for info in infos:
                node = nodes_by_name.get(info.name)
                bytes_ = float(info.byte_size)
                part = node.partitioner if node is not None else None
                is_stage = part is not None and (
                    (part.spec is not None
                     and const.PIPE_AXIS in part.spec)
                    or (part.spec is None
                        and part.mesh_axis == const.PIPE_AXIS
                        and part.num_shards > 1))
                if is_stage:
                    spec_tail = (part.spec[1:] if part.spec else [])
                    tail_axes = {a for e in spec_tail
                                 for a in (e if isinstance(e, (list, tuple))
                                           else [e]) if a}
                    tp_over_dcn = const.DCN_AXIS in tail_axes
                    tp_sharded = const.MODEL_AXIS in tail_axes \
                        or tp_over_dcn
                    # The boundary group of this var's model-parallel
                    # collectives: the model axis, times the dcn axis
                    # when a (mis-)edited plan shards across slices —
                    # those boundaries are priced at DCN below, so such
                    # plans rank strictly worse than the same degree
                    # kept within a slice (and ADT060 flags them).
                    tp_group = (tp if const.MODEL_AXIS in tail_axes
                                else 1) * (n_dcn if tp_over_dcn else 1)
                    per_dev = bytes_ / (S * (tp_group if tp_sharded
                                             else 1))
                    # ZeRO on a tp-sharded var degrades (state shards
                    # with the parameter — recorded on the lowered plan).
                    stage, p_div, g_div, opt_div = (
                        zero_divisors(node, n_data) if not tp_sharded
                        else (0, 1, 1, 1))
                    param_b += per_dev / p_div
                    grad_b += per_dev / g_div
                    mem += per_dev / p_div + per_dev / g_div \
                        + per_dev * opt_mult / opt_div
                    if stage >= 3:
                        # Stage 3: the backward grad reduce-scatter
                        # keeps the blocking wire term; the per-layer
                        # forward all-gathers (V per leaf, once per
                        # accumulation slice) are overlap-capped like
                        # the PR 2 envelope — exposed time is what the
                        # prefetched layer's own compute cannot hide,
                        # never more than the blocking gather.  The
                        # total is FLOORED at the stage-1 rs+ag pair:
                        # replication's grad all-reduce hides behind
                        # backprop just as well (XLA's scheduler, not
                        # modeled here), so crediting only stage 3 with
                        # overlap would elect it as a phantom *speed*
                        # lever on token-hinted models — it must win
                        # through the memory gate alone (the
                        # auto_strategy zoo contract, pinned by
                        # test_zero_stage_ladder_memory_and_election).
                        # The zero3_gather precision slot narrows both
                        # directions: the forward gathers ride the
                        # gather wire (true s8 at int8 — 4x), the
                        # backward cotangent reduce-scatter the summing
                        # wire (fp16 levels — 2x); q/dq passes charge
                        # against the saving.  The stage-1 floor below
                        # stays at fp32 on purpose: stage 1 is PS sync
                        # (full precision), so z3 narrowing is a wire-
                        # volume lever for the drift report, not a step-
                        # time lever past the floor.
                        f_ici, f_dcn = split_ring(n_data)
                        half = f_ici / 2.0
                        rs_bytes = accum * half * per_dev \
                            * PSUM_WIRE_FACTOR[z3_prec]
                        ag_bytes = accum * half * per_dev \
                            * GATHER_WIRE_FACTOR[z3_prec]
                        saved_bytes += 2.0 * accum * half * per_dev \
                            - rs_bytes - ag_bytes
                        qdq_s += qdq(2.0 * accum * half * per_dev / 4.0,
                                     z3_prec)
                        comm += rs_bytes
                        colls += accum   # backward grad reduce-scatters
                        t_ag = ag_bytes / bw_link
                        alpha_floor = hop_alpha * accum * V
                        t_hide = 0.0
                        if tokens:
                            # the step's matmul passes over this leaf's
                            # weights hide the next layer's gathers
                            # (elems ~ bytes/4; tokens_local is the
                            # whole step's share, accum slices included)
                            t_hide = 2.0 * tokens_local \
                                * (per_dev / 4.0) / flops_rate
                        exposed = alpha_floor + max(0.0, t_ag - t_hide)
                        stage1_pair = f_ici * per_dev / bw_link \
                            + 2.0 * hop_alpha
                        already = rs_bytes / bw_link + hop_alpha * accum
                        overlap_s += max(exposed,
                                         stage1_pair - already)
                        hidden_bytes += ag_bytes
                        extra_colls += accum * 2 * V
                        if f_dcn:
                            # Cross-slice half of the rs/ag pair: the
                            # intra-slice shard exchanged at DCN rates;
                            # never overlap-credited (no hiding modeled
                            # across the slow level).
                            rs_d = accum * (f_dcn / 2.0) * per_dev \
                                * PSUM_WIRE_FACTOR[z3_prec]
                            ag_d = accum * (f_dcn / 2.0) * per_dev \
                                * GATHER_WIRE_FACTOR[z3_prec]
                            saved_bytes += accum * f_dcn * per_dev \
                                - rs_d - ag_d
                            dcn_b += rs_d + ag_d
                            dcn_t += (rs_d + ag_d) / bw_dcn \
                                + dcn_alpha * 2 * accum
                            dcn_colls += 2 * accum
                    else:
                        f_ici, f_dcn = split_ring(n_data)
                        comm += grad_bytes(node, f_ici * per_dev)
                        if f_dcn:
                            dcn_sync(node, f_dcn * per_dev,
                                     2 if opt_div > 1 else 1)
                        colls += 2 if opt_div > 1 else 1
                    # rank >= 2 gates out the column-parallel biases
                    # (spec tail ['model']), which shard but never
                    # all-reduce activations.
                    head = spec_tail[0] if spec_tail else None
                    head_axes = {a for a in (head if isinstance(
                        head, (list, tuple)) else [head]) if a}
                    row_parallel = (len(spec_tail) >= 2 and bool(
                        head_axes & {const.MODEL_AXIS, const.DCN_AXIS}))
                    if row_parallel and tp_group > 1 and tokens:
                        width = info.shape[-1]
                        act_bytes = 2.0 * ring(tp_group) * V \
                            * tokens_local * width * _ACT_BYTES
                        mode = overlap_cfg or normalize_comm_overlap(
                            getattr(part, "comm_overlap", None))
                        # Boundary precision: the graph policy's tp_psum
                        # slot, or the per-variable partitioner record a
                        # hand-edited strategy carries (the adoption
                        # rule lower_pipeline_ir applies).
                        prec_b = tp_prec if tp_prec != "fp32" else \
                            (getattr(part, "precision", None) or "fp32")
                        act_factor = PSUM_WIRE_FACTOR[prec_b]
                        use_ring = (ring_kernel and prec_b == "int8"
                                    and mode is None and not tp_over_dcn)
                        if use_ring:
                            # EQuARX ring: TRUE s8 chunks on every hop
                            # (vs int8 levels on an fp16 wire), paid for
                            # with per-hop fused requantization passes.
                            act_factor = float(
                                kp["quant_ring_wire_factor"])
                        if prec_b != "fp32":
                            # fwd + bwd payload elements per step, each
                            # quantized before / dequantized after its
                            # collective (the ring requantizes per hop —
                            # the calibratable factor).
                            qdq_s += qdq(2.0 * V * tokens_local * width,
                                         prec_b) \
                                * (float(kp["quant_ring_qdq_factor"])
                                   if use_ring else 1.0)
                        if tp_over_dcn:
                            # Megatron boundary spanning slices: the
                            # whole per-execution payload crosses DCN
                            # every microbatch and is never overlap-
                            # credited — exactly why the search keeps
                            # tp within a slice and ADT060 flags plans
                            # that don't.
                            wired = act_bytes * act_factor
                            saved_bytes += act_bytes - wired
                            dcn_b += wired
                            dcn_t += wired / bw_dcn \
                                + dcn_alpha * 2 * M * V
                            dcn_colls += 2 * M * V
                        elif mode is None:
                            comm += act_bytes * act_factor
                            saved_bytes += act_bytes * (1.0 - act_factor)
                            # The ring pays 2(n-1) hop launches per
                            # boundary where the monolithic collective
                            # pays one — part of the crossover the
                            # election trades against the wire saving.
                            colls += 2 * M * V * (
                                2 * (tp_group - 1) if use_ring else 1)
                        else:
                            # Latency-hiding decomposition: price the
                            # Megatron boundary as max(comm, compute)
                            # instead of comm + compute.  Per chunk
                            # execution and direction, the blocking
                            # envelope is the ring all-reduce
                            #   t_blk = 2(tp-1)·t_wire + α
                            # (t_wire = one chunk's hop transfer).  The
                            # collective matmul exposes only what chunk
                            # compute cannot hide:
                            #   t_mm = (tp-1)·(max(0, t_hop − t_chunk)
                            #           + t_hop)
                            # (rs-phase hops hidden behind per-chunk
                            # matmuls; the closing ag-phase is bare),
                            # and the rs+ag pair exposes
                            #   t_rsag = max(α, 2(tp-1)·t_hop
                            #            − tp·t_chunk)
                            # (whole-layer overlap via XLA's async
                            # scheduler).  Each is capped at t_blk —
                            # the lowering can always fall back to the
                            # fused all-reduce, so a decomposed plan
                            # never prices above the blocking one.
                            execs = M * V
                            tok_e = tokens_local / max(M, 1)
                            contract = float(math.prod(
                                info.shape[1:-1])) or 1.0
                            t_chunk = 2.0 * tok_e * (contract / tp) \
                                * (width / tp) / flops_rate
                            t_wire = tok_e * (width / tp) * _ACT_BYTES \
                                * act_factor / bw_link
                            # The fused collective-matmul kernel issues
                            # each hop's accumulate+matmul (and, on
                            # silicon, its RDMA) as ONE op — the per-hop
                            # launch overhead drops to the calibratable
                            # fused constant.
                            mm_alpha = (float(kp["fused_hop_alpha_s"])
                                        if fused_mm and mode == "matmul"
                                        else hop_alpha)
                            t_hop = t_wire + hop_alpha
                            t_hop_mm = t_wire + mm_alpha
                            t_blk = 2.0 * (tp - 1) * t_wire + hop_alpha
                            t_rsag = max(hop_alpha,
                                         2.0 * (tp - 1) * t_hop
                                         - tp * t_chunk)
                            t_mm = (tp - 1) * (
                                max(0.0, t_hop_mm - t_chunk) + t_hop_mm)
                            fwd_t = min(t_mm if mode == "matmul"
                                        else t_rsag, t_blk)
                            # The column partner's backward cotangent
                            # reduction decomposes as rs+ag in either
                            # mode (no matmul of its own to hide
                            # behind); charged here like the blocking
                            # model charges its 2x on the row var.
                            bwd_t = min(t_rsag, t_blk)
                            overlap_s += execs * (fwd_t + bwd_t)
                            hidden_bytes += act_bytes * act_factor
                            saved_bytes += act_bytes * (1.0 - act_factor)
                            extra_colls += execs * (
                                (tp + 1 if mode == "matmul" else 2) + 2)
                else:
                    # Shared (non-stage) variable.  Vocab parallelism
                    # (model axis in a shared var's spec) stores the tied
                    # embedding at 1/tp per device — params, grads, AND
                    # optimizer state all shrink — and the pipe x data
                    # grad sync moves 1/tp the bytes.  ZeRO on the
                    # model-sharded table shards its optimizer state
                    # *additionally* over pipe x data (state at
                    # 1/(tp·pipe·data)); its params/grads stay 1/tp
                    # (a stage-3 request degrades to this form).  A
                    # model-replicated shared var takes the full stage
                    # ladder over pipe x data.
                    v_sharded = (part is not None and part.spec
                                 and const.MODEL_AXIS in part.spec)
                    vsh = tp if v_sharded else 1
                    per_dev = bytes_ / vsh
                    n_pd = S * n_data
                    stage, p_div, g_div, opt_div = zero_divisors(node, n_pd)
                    if v_sharded:
                        p_div = g_div = 1   # param already 1/tp-stored
                    param_b += per_dev / p_div
                    grad_b += per_dev / g_div
                    mem += per_dev / p_div + per_dev / g_div \
                        + per_dev * opt_mult / opt_div
                    if stage >= 3 and not v_sharded:
                        f_ici, f_dcn = split_ring(n_pd)
                        half = f_ici / 2.0
                        rs_sh = accum * half * per_dev \
                            * PSUM_WIRE_FACTOR[z3_prec]
                        ag_sh = accum * half * per_dev \
                            * GATHER_WIRE_FACTOR[z3_prec]
                        saved_bytes += 2.0 * accum * half * per_dev \
                            - rs_sh - ag_sh
                        qdq_s += qdq(2.0 * accum * half * per_dev / 4.0,
                                     z3_prec)
                        comm += rs_sh
                        colls += accum   # backward grad reduce-scatters
                        t_ag = ag_sh / bw_link
                        overlap_s += t_ag + hop_alpha * accum
                        hidden_bytes += ag_sh
                        extra_colls += accum * 2
                        if f_dcn:
                            rs_d = accum * (f_dcn / 2.0) * per_dev \
                                * PSUM_WIRE_FACTOR[z3_prec]
                            ag_d = accum * (f_dcn / 2.0) * per_dev \
                                * GATHER_WIRE_FACTOR[z3_prec]
                            saved_bytes += accum * f_dcn * per_dev \
                                - rs_d - ag_d
                            dcn_b += rs_d + ag_d
                            dcn_t += (rs_d + ag_d) / bw_dcn \
                                + dcn_alpha * 2 * accum
                            dcn_colls += 2 * accum
                    else:
                        f_ici, f_dcn = split_ring(n_pd)
                        comm += grad_bytes(node, f_ici * per_dev)
                        if f_dcn:
                            dcn_sync(node, f_dcn * per_dev,
                                     2 if opt_div > 1 else 1)
                        colls += 2 if opt_div > 1 else 1
                    # Track the unembedding for the loss-head epilogue
                    # pricing below.  Identification priority: a
                    # model-sharded spec (the strategy SAYS which var is
                    # the vocab table), then the vocab-rule naming
                    # (…/embedding — so the replicated baseline of a
                    # small-vocab long-context model doesn't mistake
                    # pos_embed for the unembedding), then largest
                    # rank-2 shared var; bytes break ties within a tier.
                    if len(info.shape) == 2:
                        prio = (2 if v_sharded else
                                1 if _VOCAB_NAME_RE.search(info.name)
                                else 0)
                        if emb_var is None or (prio, bytes_) > emb_var[0]:
                            emb_var = ((prio, bytes_), info.shape[0],
                                       info.shape[1], vsh)
            if tokens and emb_var is not None:
                # Loss-head epilogue: the [tokens_local, V] fp32 logits
                # buffer dominates HBM as vocab grows; vocab parallelism
                # bounds it at 1/tp (the streaming chunked epilogue never
                # materializes more than its local shard), replacing the
                # replicated [B,L,H]x[H,V] matmul with a sharded one plus
                # psums: the prologue lookup psum + 3 token-shaped stat
                # psums (max, sum-exp, target logit) forward, one hidden-
                # state cotangent psum backward.
                _, V_dim, width, vsh = emb_var
                tokens_local = tokens / max(n_data, 1)
                # 1/vsh is an upper bound for the sharded case: the
                # streaming epilogue further bounds the live buffer to
                # [B, chunk, V/tp], but the model only knows tokens
                # (B x L fused), not the B/L split the chunk bound
                # needs — so it prices the conservative full-sequence
                # shard.  Safe direction for the feasibility gate: it
                # can under-elect vocab parallelism, never over-elect.
                peak_logits = tokens_local * V_dim * 4.0 / vsh
                mem += peak_logits
                if vsh > 1:
                    # The prologue lookup psum rides the tp_psum slot
                    # (it IS a sum_partials boundary); the stat psums
                    # and backward hidden-cotangent psum ride
                    # vocab_stats.
                    lk_bytes = ring(tp) * tokens_local * width * 4.0
                    st_bytes = ring(tp) * tokens_local \
                        * (width + 3.0) * 4.0
                    lk_f = PSUM_WIRE_FACTOR[tp_prec]
                    st_f = PSUM_WIRE_FACTOR[stats_prec]
                    comm += lk_bytes * lk_f + st_bytes * st_f
                    saved_bytes += lk_bytes * (1.0 - lk_f) \
                        + st_bytes * (1.0 - st_f)
                    qdq_s += qdq(tokens_local * width, tp_prec) \
                        + qdq(tokens_local * (width + 3.0), stats_prec)
                    colls += 6
            if tokens:
                # activation hop per schedule tick (ppermute ring), fwd +
                # transposed bwd; T = M*V + S - 1 ticks of a microbatch
                # activation (tokens_local/M x hidden) — interleaving
                # trades V-fold more (smaller) hops for a ~V-fold smaller
                # bubble, which only measurement can arbitrate.
                tokens_local = tokens / max(n_data, 1)
                T = M * V + S - 1
                comm += 2.0 * T * (tokens_local / M) * hidden * _ACT_BYTES
                colls += 2 * T
                # The [M, B/M, hidden] output buffer rides the tick scan
                # on every device regardless of remat.
                mem += tokens_local * hidden * _ACT_BYTES
                remat = bool(strategy.graph_config.parallel.get(
                    "remat", False))
                if act_hint:
                    if remat:
                        # jax.checkpoint around each chunk: only the
                        # chunk boundary inputs stay live across the
                        # schedule — M*V executions x (tokens_local/M)
                        # boundary tokens x hidden.
                        mem += V * tokens_local * hidden * _ACT_BYTES
                    else:
                        # AD through the tick scan keeps every chunk
                        # execution's residuals: M*V executions, each
                        # holding its 1/(S*V) share of the per-token
                        # fwd+bwd footprint -> act_hint*tokens_local/S.
                        mem += act_hint * tokens_local / S
        else:  # expert
            E = mesh.get(const.EXPERT_AXIS, 1)
            # dense params replicate + sync over data x expert (PS ->
            # ZeRO-1 over both); expert tables live 1/E and sync over
            # data only (PS degrades to plain there — state already
            # sharded with the table).
            for info in infos:
                node = nodes_by_name.get(info.name)
                bytes_ = float(info.byte_size)
                part = node.partitioner if node is not None else None
                is_expert = part is not None and (
                    (part.spec is not None and const.EXPERT_AXIS in part.spec)
                    or part.mesh_axis == const.EXPERT_AXIS)
                if is_expert:
                    mem += bytes_ * (2.0 + opt_mult) / E
                    param_b += bytes_ / E
                    grad_b += bytes_ / E
                    f_ici, f_dcn = split_ring(n_data)
                    comm += grad_bytes(node, f_ici * (bytes_ / E))
                    if f_dcn:
                        dcn_sync(node, f_dcn * (bytes_ / E))
                    colls += 1
                else:
                    n_sync = n_data * E
                    stage, p_div, g_div, opt_div = zero_divisors(node,
                                                                 n_sync)
                    param_b += bytes_ / p_div
                    grad_b += bytes_ / g_div
                    mem += bytes_ / p_div + bytes_ / g_div \
                        + bytes_ * opt_mult / opt_div
                    f_ici, f_dcn = split_ring(n_sync)
                    mult = accum if stage >= 3 else 1
                    comm += grad_bytes(node, mult * f_ici * bytes_)
                    if f_dcn:
                        dcn_sync(node, mult * f_dcn * bytes_,
                                 2 * accum if stage >= 3
                                 else 2 if opt_div > 1 else 1)
                    colls += (2 * accum if stage >= 3
                              else 2 if opt_div > 1 else 1)
            if tokens and E > 1:
                # Hierarchical all_to_all term: dispatch + combine, fwd
                # + bwd — 4 passes of the capacity-padded routed slots.
                # Top-2 routing fills E x C = 2 x cf x G slots, so the
                # [E, C, M] payload is (2 x capacity_factor) local token
                # activations, (E-1)/E of it leaving the device.
                knobs = strategy.graph_config.parallel
                cap_f = float(knobs.get("capacity_factor", 2.0))
                over_dcn = bool(knobs.get("expert_over_dcn", False))
                a2a_prec = policy.get("moe_a2a", "fp32")
                payload = 4.0 * (2.0 * cap_f) * tokens_per_dev * hidden \
                    * _ACT_BYTES * (E - 1) / E
                # Permute-shaped: the wire narrows like a gather (true
                # s8 at int8 — no summing, no fp16-levels headroom).
                factor = GATHER_WIRE_FACTOR[a2a_prec]
                a2a_kernel = ("a2a_ring" in kern_cfg
                              and a2a_prec == "int8" and not over_dcn)
                if a2a_kernel:
                    factor = float(kp["a2a_ring_wire_factor"])
                wired = payload * factor
                saved_bytes += payload - wired
                if a2a_prec != "fp32":
                    # whole payload quantized before / dequantized after
                    # each pass; the fused ring does both inside the hop
                    # (the calibratable VMEM-vs-HBM factor).
                    qdq_a2a = qdq(payload / _ACT_BYTES, a2a_prec) \
                        * (float(kp["a2a_ring_qdq_factor"])
                           if a2a_kernel else 1.0)
                    qdq_s += qdq_a2a
                    a2a_t += qdq_a2a
                # The ring decomposes each all_to_all into E-1 ppermute
                # hops (2(E-1) per dispatch+combine pair — the ADT120
                # wire signature); the monolithic collective is one
                # launch per pass.
                a2a_launches = 4 * (E - 1) if a2a_kernel else 4
                if over_dcn:
                    # Expert axis spanning slices: every routed slot
                    # crosses DCN each pass, never overlap-credited —
                    # exactly why the search keeps experts within a
                    # slice (ADT061 flags plans that don't) unless the
                    # topology's link constants invert the trade.
                    dcn_b += wired
                    t = wired / bw_dcn + dcn_alpha * a2a_launches
                    dcn_t += t
                    a2a_t += t
                    dcn_colls += a2a_launches
                elif a2a_kernel:
                    # Fused ring: the kernel issues each hop's ppermute
                    # (and on silicon its RDMA), so the 4(E-1) launches
                    # are priced at the calibratable fused alpha — the
                    # composed monolithic collective pays the full
                    # hop_alpha per pass.  This launch trade (against
                    # the halved q/dq above) is the ring-vs-composed
                    # crossover the search arbitrates.
                    comm += wired
                    extra_colls += a2a_launches
                    t_launch = float(kp["fused_hop_alpha_s"]) \
                        * a2a_launches
                    overlap_s += t_launch
                    a2a_t += wired / bw_link + t_launch
                else:
                    comm += wired
                    colls += a2a_launches
                    a2a_t += wired / bw_link + hop_alpha * a2a_launches
                a2a_b += wired
            if tokens and act_hint:
                mem += act_hint * tokens_per_dev
        comm_time = ((comm / bw_link + hop_alpha * colls + overlap_s
                      + qdq_s + dcn_t)
                     if total_devices > 1 else 0.0)
        hbm = self.chip.hbm_gb * 1e9 * self.hbm_headroom
        return StrategyCost(comm_bytes=comm + hidden_bytes + dcn_b,
                            comm_time_s=comm_time,
                            num_collectives=colls + extra_colls
                            + dcn_colls,
                            mem_bytes_per_device=mem,
                            feasible=mem <= hbm,
                            overlap_time_s=(overlap_s
                                            if total_devices > 1 else 0.0),
                            peak_logits_bytes=(peak_logits
                                               if kind == "pipeline"
                                               else 0.0),
                            param_shard_bytes=param_b,
                            grad_shard_bytes=grad_b,
                            wire_bytes_saved=saved_bytes,
                            quant_dq_time_s=(qdq_s if total_devices > 1
                                             else 0.0),
                            dcn_bytes=dcn_b,
                            dcn_time_s=(dcn_t if total_devices > 1
                                        else 0.0),
                            a2a_bytes=a2a_b,
                            a2a_time_s=(a2a_t if total_devices > 1
                                        else 0.0))

    # ------------------------------------------------------------------ #
    # Serving: per-token decode latency
    # ------------------------------------------------------------------ #
    def decode_cost(self, trainable: Trainable, config,
                    *, batch_slots: int = 1, max_len: int = 2048,
                    kv_bytes_per_elem: float = _ACT_BYTES,
                    mean_request_len: Optional[float] = None,
                    mean_prompt_len: Optional[float] = None,
                    kv_block_len: int = 16,
                    prefix_hit_rate: float = 0.0,
                    spec_acceptance: Optional[float] = None,
                    loop_steps: int = 1, block=None) -> DecodeCost:
        """Per-token decode latency for one serving config.

        ``config`` is either a training :class:`Strategy` (its Strategy-
        IR parallel knobs seed the serving shape — the same IR answers
        both objectives) or a plain dict with ``tensor_parallel`` /
        ``vocab_parallel`` / ``kv_layout`` keys.  The model:

        * **compute** — a decode token's matmul passes touch every
          parameter once (2 FLOPs/element), divided across the tp group
          for the vars the Megatron/vocab rule tables shard (the same
          tables the serving engine shards by);
        * **comm** — per layer, the row-parallel boundary all-reduces of
          the ``[B, H]`` activations (attention out-proj + mlp ``wo``,
          forward only — decode has no backward), plus the
          vocab-parallel epilogue's lookup psum and greedy pmax/pmin;
        * **memory** — sharded parameters + the TP-sharded KV cache
          (``2·layers·H·max_len·slots/tp`` elements; paged: the mean
          request length rounded up to ``kv_block_len`` per slot),
          gated against HBM headroom like the training costs;
        * **capacity** — ``request_capacity``: concurrent requests the
          post-params HBM supports under ``mean_request_len`` (default:
          every request fills ``max_len`` — the no-variance worst
          case).  Dense reserves a full ``max_len`` lane per request;
          paged reserves ``ceil(mean/block)·block`` positions and pays
          the calibratable ``paged_attention_overhead`` on the
          attention term — so :attr:`DecodeCost.serve_score` elects
          paged exactly when length variance makes dense reservation
          wasteful, and dense when it doesn't (both directions pinned);
        * **fleet** — a ``replicas`` key prices the router's shape: the
          tp group must fit a slice's ICI (rejected otherwise — the
          serving ADT060 analog), ``replicas × tp`` must fit the
          topology, and a fleet spanning slices pays the per-request
          DCN dispatch hop amortized per token
          (:attr:`DecodeCost.dispatch_time_s`) —
          :attr:`DecodeCost.fleet_score` then ranks aggregate
          throughput for the mix.
        * **the throughput ladder (PR 16)** — ``prefix_caching``
          divides the capacity term's per-request residency by the
          traffic's ``prefix_hit_rate`` (the shared leading blocks cost
          the pool nothing) and pays the calibratable
          ``prefix_caching_overhead`` on attention, so the capacity
          objective elects it exactly when the mix actually shares
          prefixes; ``speculative=k`` prices the window — draft
          proposes ``k`` at ``spec_draft_flops_frac``, one verify
          dispatch scores ``k+1`` at ``spec_marginal_token_cost`` per
          extra token — divided by the expected emissions
          ``(1 - α^{k+1}) / (1 - α)`` under acceptance rate
          ``spec_acceptance`` (default: the profile's
          ``spec_acceptance_default``), so the latency objective elects
          speculation exactly when α clears the draft+verify overhead
          — both directions pinned.
        * **disaggregation (PR 17)** — ``prefill_replicas`` +
          ``decode_replicas`` keys price a prefill/decode pool split:
          a request's prompt pass runs on the prefill pool, its KV
          prefix is handed to the decode pool (ICI within a slice, DCN
          when the split spans slices — the handoff term), and its
          decode tail runs there.  ``mean_prompt_len`` splits the
          traffic's ``mean_request_len`` into prompt vs decoded tokens
          (default: half) — :attr:`DecodeCost.disagg_score` then ranks
          splits by the bottleneck stage, so prefill-bound and
          decode-bound mixes elect different splits (both directions
          pinned on the handoff term).
        * **a looped stack** — ``loop_steps`` (the block spec's
          ``BlockSpec.loop_steps``; 1 for every other model): a token
          passes the stacked layers that many times, so the stage
          parameters' FLOPs, the attention term, the tp boundaries and
          the KV elements a position holds (one set of keys and values
          per pass and layer) all multiply by it; the parameters'
          bytes do not — there is one set of weights.
        * **a mixed or routed stack** — ``block`` (the model's
          ``BlockSpec``; its ``loop_steps`` then stands for the
          argument): only the ``"full"`` layers of ``layer_period``
          cache keys and values, ``kv_heads x head_dim`` wide (its
          ``"latent"`` layers a row, below), and pay the attention
          term; each ``"linear"`` layer instead reads and
          writes a float32 state of ``value_heads x key_dim x
          value_dim`` a slot every token, priced at the chip's HBM
          rate (``state_time_s``) and held a slot in memory and in the
          capacity term.  A routed FFN's experts (leaves under
          ``experts/``) are not every parameter once: a row multiplies
          the ``top_k / num_experts`` of them it chose, and a step
          reads the held experts some row hit — ``held x (1 - (1 -
          top_k / num_experts) ^ slots)`` in expectation — at the HBM
          rate; the larger of the two is their price.  The experts are
          priced by their leaves, so a stack whose ``dense_layers``
          leading layers carry a dense FFN pays those every parameter
          once, like any dense leaf.
        * **a latent row** — ``block.latent``: a cached position of a
          layer is ONE row of ``kv_rank + rope_dim`` values that every
          query head reads, not ``heads x head_dim x 2``: the cache's
          bytes, the capacity term and the attention term — the rows of
          a lane read once a layer at the chip's HBM rate, which is what
          binds a step of a few query rows — are of that row.
        """
        from autodist_tpu.strategy.ir import (normalize_kernel,
                                              normalize_kv_layout,
                                              normalize_prefill_chunk,
                                              normalize_prefix_caching,
                                              normalize_speculative)

        if isinstance(config, Strategy):
            par = config.graph_config.parallel or {}
            kern = normalize_kernel(
                getattr(config.graph_config, "kernel", None))
        else:
            par = config
            kern = normalize_kernel(config.get("kernel"))
        tp = int(par.get("tensor_parallel", 1) or 1)
        vocab_parallel = bool(par.get("vocab_parallel", False))
        kv_layout = normalize_kv_layout(par.get("kv_layout"))
        replicas = int(par.get("replicas", 1) or 1)
        prefill_chunk = normalize_prefill_chunk(par.get("prefill_chunk"))
        prefix_caching = normalize_prefix_caching(
            par.get("prefix_caching", False))
        spec_k = normalize_speculative(par.get("speculative"))
        prefill_r = int(par.get("prefill_replicas", 0) or 0)
        decode_r = int(par.get("decode_replicas", 0) or 0)
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if bool(prefill_r) != bool(decode_r):
            raise ValueError(
                "a disaggregated split names BOTH pools: got "
                f"prefill_replicas={prefill_r}, "
                f"decode_replicas={decode_r}")
        if prefill_r and replicas > 1:
            raise ValueError(
                "replicas and a prefill/decode pool split are exclusive "
                "shapes — the pool split IS the fleet shape")
        if prefill_r and kv_layout != "paged":
            raise ValueError(
                "the prefill->decode KV handoff moves block-table "
                "prefixes — a pool split requires kv_layout='paged'")
        if (prefill_chunk is not None or prefix_caching) \
                and kv_layout != "paged":
            raise ValueError(
                "prefill_chunk/prefix_caching ride the block table — "
                "they require kv_layout='paged'")
        if not 0.0 <= float(prefix_hit_rate) <= 1.0:
            raise ValueError(
                f"prefix_hit_rate must be in [0, 1], got "
                f"{prefix_hit_rate}")
        # The fleet placement contract (arxiv 2110.10548's hierarchy,
        # serving-side): tp's per-layer boundary all-reduces live on
        # every decoded token, so the tp group must stay within a
        # slice's ICI; only the router's per-REQUEST dispatch may cross
        # DCN — replicas spread across slices, tp never does.
        num_devices = self.spec.num_devices()
        num_slices = max(int(getattr(self.spec, "num_slices", 1) or 1), 1)
        per_slice = num_devices // num_slices
        if tp > per_slice:
            raise ValueError(
                f"tensor_parallel={tp} exceeds the {per_slice} devices "
                f"a slice's ICI connects ({num_slices} slice(s) of "
                f"{per_slice}); tp must stay within a slice — spread "
                "replicas across slices instead")
        if replicas * tp > num_devices:
            raise ValueError(
                f"replicas={replicas} x tensor_parallel={tp} needs "
                f"{replicas * tp} devices; the topology has "
                f"{num_devices}")
        if prefill_r and (prefill_r + decode_r) * tp > num_devices:
            raise ValueError(
                f"pool split prefill={prefill_r} + decode={decode_r} "
                f"at tensor_parallel={tp} needs "
                f"{(prefill_r + decode_r) * tp} devices; the topology "
                f"has {num_devices} (the ADT089 bound)")
        flash = "flash_decode" in kern
        from autodist_tpu.strategy.parallel_builders import (
            PIPELINE_TP_RULES, PIPELINE_VOCAB_RULES)

        tp_res = [re.compile(p) for p, _ in PIPELINE_TP_RULES]
        v_res = [re.compile(p) for p, _ in PIPELINE_VOCAB_RULES]
        hidden = self._hidden_dim(trainable)
        layers = getattr(trainable, "num_stages", None)
        if layers is None:
            # Fallback for non-stage-structured trainables: the most
            # common leading dim among rank>=3 vars (a stacked layer
            # stack's shared leading extent).  Rank-2 tables are
            # excluded on purpose — a [V, H] embedding's vocab dim
            # would otherwise masquerade as a layer count and inflate
            # every term by orders of magnitude.
            import collections as _collections

            leads = _collections.Counter(
                i.shape[0] for i in trainable.var_infos()
                if len(i.shape) >= 3)
            layers = leads.most_common(1)[0][0] if leads else 1
        passes = int(loop_steps if block is None else block.loop_steps)
        if passes < 1:
            raise ValueError(f"loop_steps must be >= 1, got {loop_steps}")
        # what a token passes, and what the cache holds a layer of
        kinds = block.layer_kinds(int(layers)) if block is not None \
            else ("full",) * int(layers)
        linear_layers = kinds.count("linear")
        layers = (len(kinds) - linear_layers) * passes
        moe = getattr(block, "moe", None)
        hbm_rate = self.chip.hbm_gbps * 1e9
        elems = bytes_ = expert_elems = expert_bytes = 0.0
        for info in trainable.var_infos():
            shard = 1
            if tp > 1:
                name = info.name
                short = name.split("/", 1)[1] if "/" in name else name
                if any(p.search(name) for p in tp_res):
                    shard = tp
                elif vocab_parallel and any(p.search(short)
                                            for p in v_res):
                    shard = tp
            # the stacked layers' weights multiply every pass's
            # activations; embedding, head and final norm once
            stacked = info.name.startswith("stages/")
            bytes_ += info.byte_size / shard
            if moe is not None and "/experts/" in info.name:
                expert_elems += info.size
                expert_bytes += info.byte_size
                continue
            elems += info.size / shard * (passes if stacked else 1)
        mxu_eff = float(self.link_profile.get(
            "mxu_efficiency", _DEFAULT_MXU_EFFICIENCY))
        flops_rate = self.chip.peak_bf16_tflops * 1e12 * mxu_eff
        compute = 2.0 * elems * batch_slots / flops_rate
        if moe is not None:
            chosen = moe.top_k / moe.num_experts
            hit = 1.0 - (1.0 - chosen) ** batch_slots
            compute += max(
                2.0 * expert_elems * chosen * batch_slots / flops_rate,
                expert_bytes * hit / hbm_rate)
        # a linear layer's float32 state a slot, there and back a token
        state_bytes = 0.0
        if linear_layers:
            # the rule's matrix (and, of power retention, its normaliser)
            state_bytes = 4.0 * linear_layers * block.linear.state_floats
        state_time = 2.0 * state_bytes * batch_slots / hbm_rate
        compute += state_time
        kv_width = hidden
        if block is not None and block.kv_heads and block.head_dim:
            kv_width = block.kv_heads * block.head_dim
        # what a position holds a layer: keys and values, or the one row
        latent = getattr(block, "latent", None)
        position_elems = 2.0 * kv_width if latent is None else latent.row
        # Attention over the cache: per token, each layer contracts the
        # query against its [heads/tp, max_len, head_dim] cache slice
        # twice (scores + values) — the term that grows with occupancy
        # and the one the flash_decode kernel moves.  Past the
        # calibrated crossover length flash divides it by the measured
        # speedup; below it the kernel's fixed overhead loses to plain
        # einsum (the short penalty < 1), so the election flips exactly
        # at the crossover.
        attn = 4.0 * layers * hidden * max_len * batch_slots \
            / max(tp, 1) / flops_rate
        if flash:
            kp = self.kernel_profile
            if max_len >= float(kp["flash_decode_crossover_len"]):
                attn /= float(kp["flash_decode_speedup"])
            else:
                attn /= float(kp["flash_decode_short_penalty"])
        if kv_layout == "paged":
            # The block-table indirection: gathers (composed) or
            # per-block DMA setup (the paged flash kernel) vs the
            # dense contiguous lane.
            attn *= float(self.kernel_profile.get(
                "paged_attention_overhead",
                KERNEL_PROFILE["paged_attention_overhead"]))
        if prefix_caching:
            # CoW bookkeeping on the gather path: refcount checks plus
            # the occasional copy-before-write.  Strictly > 1 so a mix
            # with no sharing (hit rate 0) never elects the rung for
            # free — the hit rate has to buy the overhead back through
            # the capacity term (both directions pinned).
            attn *= float(self.kernel_profile.get(
                "prefix_caching_overhead",
                KERNEL_PROFILE["prefix_caching_overhead"]))
        if latent is not None:
            # every query head on the one row: a lane's rows once a layer
            attn = layers * position_elems * kv_bytes_per_elem * max_len \
                * batch_slots / hbm_rate
        compute += attn

        bw_link = float(self.link_profile.get(
            "ici_gbps", self.chip.ici_gbps)) * 1e9
        hop_alpha = float(self.link_profile.get(
            "hop_alpha_s", COLLECTIVE_ALPHA))
        ring_m = 2.0 * (tp - 1) / tp if tp > 1 else 0.0
        comm = 0.0
        if tp > 1:
            boundaries = 2 * layers + (1 if vocab_parallel else 0)
            comm = ring_m * boundaries * batch_slots * hidden * _ACT_BYTES \
                / bw_link + hop_alpha * (boundaries
                                         + (2 if vocab_parallel else 0))
        # Speculative decoding reprices the whole window: one target
        # step becomes draft-proposes-k (a draft forward costs
        # spec_draft_flops_frac of the target's) plus one verify
        # dispatch scoring k+1 positions (each extra position costs
        # spec_marginal_token_cost of a full step — the matmuls batch,
        # only attention and the epilogue grow).  The window emits
        # E = (1 - α^{k+1}) / (1 - α) tokens in expectation under
        # acceptance rate α, so every per-token term divides by E.
        # α below the break-even leaves token_time_s WORSE than
        # vanilla — the ladder rung loses the election, as it should.
        spec_alpha = 0.0
        if spec_acceptance is not None \
                and not 0.0 <= float(spec_acceptance) <= 1.0:
            raise ValueError(
                f"spec_acceptance must be in [0, 1], got "
                f"{spec_acceptance}")
        if spec_k is not None:
            kp = self.kernel_profile
            alpha = float(kp.get(
                "spec_acceptance_default",
                KERNEL_PROFILE["spec_acceptance_default"])
                if spec_acceptance is None else spec_acceptance)
            spec_alpha = alpha
            k = int(spec_k)
            expected = (float(k + 1) if alpha >= 1.0
                        else (1.0 - alpha ** (k + 1)) / (1.0 - alpha))
            marginal = float(kp.get(
                "spec_marginal_token_cost",
                KERNEL_PROFILE["spec_marginal_token_cost"]))
            draft_frac = float(kp.get(
                "spec_draft_flops_frac",
                KERNEL_PROFILE["spec_draft_flops_frac"]))
            window_scale = (1.0 + k * marginal + k * draft_frac) \
                / expected
            compute *= window_scale
            attn *= window_scale
            # The verify dispatch is ONE program — its tp boundary
            # all-reduces fire once per window, not once per token.
            comm /= expected
        # Per-request cache residency: dense reserves the full max_len
        # lane whatever the request's length; paged reserves the mean
        # length rounded up to a block.
        mean_len = float(max_len if mean_request_len is None
                         else min(mean_request_len, max_len))
        bl = max(int(kv_block_len), 1)
        resident = (float(-(-int(math.ceil(mean_len)) // bl) * bl)
                    if kv_layout == "paged" else float(max_len))
        lane_bytes = layers * position_elems * kv_bytes_per_elem \
            / max(tp, 1)
        kv = lane_bytes * resident * batch_slots
        mem = bytes_ + kv + state_bytes * batch_slots
        if spec_k is not None:
            # The draft rides along: its params + its full-capacity
            # block pool cost spec_draft_flops_frac of the target's.
            draft_frac = float(self.kernel_profile.get(
                "spec_draft_flops_frac",
                KERNEL_PROFILE["spec_draft_flops_frac"]))
            mem += draft_frac * (bytes_ + kv)
        hbm = self.chip.hbm_gb * 1e9 * self.hbm_headroom
        # Prefix caching: the shared leading run of a request's blocks
        # is refcounted, not duplicated — at hit rate h each admission
        # charges the pool only the novel (1 - h) suffix (floored at
        # one block: the CoW-protected partial tail is always
        # physically owned somewhere).
        resident_eff = resident
        if prefix_caching:
            resident_eff = max(resident * (1.0 - float(prefix_hit_rate)),
                               float(bl))
        capacity = max(hbm - bytes_, 0.0) / max(
            lane_bytes * resident_eff + state_bytes, 1e-30)
        if spec_k is not None:
            capacity /= 1.0 + draft_frac
        # Router dispatch across DCN: a fleet too big for one slice
        # spreads replicas across slices, and a request routed to a
        # remote-slice replica ships its prompt over DCN once —
        # amortized over the tokens it then decodes locally.  A fleet
        # that fits one slice pays nothing (the both-ways pin: replicas
        # are PRICED across DCN, never free, never forbidden).
        dispatch = 0.0
        if replicas > 1 and replicas * tp > per_slice:
            bw_dcn, dcn_alpha = self._dcn_link()
            remote_frac = (num_slices - 1) / num_slices
            prompt_bytes = mean_len * 4.0   # token ids on the wire
            dispatch = remote_frac * (dcn_alpha
                                      + prompt_bytes / bw_dcn) \
                / max(mean_len, 1.0)
        # Disaggregation: split the mix's mean request into its prompt
        # (prefill-pool work) and decoded tail (decode-pool work), and
        # price the per-request KV prefix handoff between the pools —
        # ICI when the whole split fits one slice, DCN when it spans
        # slices.  The handoff lands on the DECODE stage (its pool
        # absorbs the ingest), so a split that starves decode pays for
        # every handoff it forces — the term disagg_score pins on.
        prefill_t = decode_t = handoff = 0.0
        if prefill_r >= 1 and decode_r >= 1:
            prompt_len = float(mean_len / 2.0 if mean_prompt_len is None
                               else min(mean_prompt_len, mean_len))
            if prompt_len < 0:
                raise ValueError(
                    f"mean_prompt_len must be >= 0, got {prompt_len}")
            decode_tokens = max(mean_len - prompt_len, 1.0)
            prefill_t = 2.0 * elems * prompt_len / flops_rate
            decode_t = (compute + comm) * decode_tokens
            hand_bytes = lane_bytes * prompt_len
            if (prefill_r + decode_r) * tp > per_slice:
                bw_dcn, dcn_alpha = self._dcn_link()
                handoff = dcn_alpha + hand_bytes / bw_dcn
            else:
                handoff = hop_alpha + hand_bytes / bw_link
        return DecodeCost(token_time_s=compute + comm, comm_time_s=comm,
                          compute_time_s=compute, kv_bytes_per_device=kv,
                          mem_bytes_per_device=mem, feasible=mem <= hbm,
                          tensor_parallel=tp, vocab_parallel=vocab_parallel,
                          attn_time_s=attn, kernel=tuple(sorted(kern)),
                          kv_layout=kv_layout,
                          request_capacity=capacity,
                          replicas=replicas, dispatch_time_s=dispatch,
                          prefill_chunk=prefill_chunk,
                          prefix_caching=prefix_caching,
                          prefix_hit_rate=(float(prefix_hit_rate)
                                           if prefix_caching else 0.0),
                          speculative=spec_k,
                          spec_acceptance=spec_alpha,
                          prefill_replicas=prefill_r,
                          decode_replicas=decode_r,
                          prefill_time_s=prefill_t,
                          decode_time_s=decode_t,
                          handoff_time_s=handoff,
                          state_time_s=state_time)

    def strategy_cost(self, trainable: Trainable,
                      strategy: Strategy) -> StrategyCost:
        if strategy.graph_config.lowering == "gspmd":
            return self._gspmd_cost(trainable, strategy)
        if strategy.graph_config.lowering in ("sequence", "pipeline",
                                              "expert"):
            return self._parallel_cost(trainable, strategy)
        n = max(strategy.graph_config.replicas, 1)
        infos = {v.name: v for v in trainable.var_infos()}
        # Hierarchical split of the replica sync: the dcn-crossing part
        # of every collective is priced at DCN constants, never at
        # ici_gbps (pure-ICI topologies keep today's exact factors).
        try:
            n_dcn = self._dcn_degree(self.spec.resolved_mesh_shape())
        except (ValueError, RuntimeError):
            n_dcn = max(int(getattr(self.spec, "num_slices", 1) or 1), 1)
        if n % max(n_dcn, 1):
            n_dcn = 1
        ring, dcn_factor = self._split_ring(n, n_dcn)
        bw_dcn, dcn_alpha = self._dcn_link()
        sparse_frac = (n_dcn - 1) / n_dcn if n_dcn > 1 else 0.0
        dcn_bytes = dcn_time = 0.0
        dcn_colls = 0

        comm_bytes = 0.0
        mem_bytes = 0.0
        groups: set = set()
        num_collectives = 0
        for node in strategy.node_configs:
            info = infos.get(node.var_name)
            if info is None:
                continue
            bytes_ = float(info.byte_size)
            sharded = node.partitioner is not None
            sync = node.synchronizer
            factor = COMPRESSOR_FACTOR.get(
                (getattr(sync, "compressor", "none") or "none")
                .partition(":")[0], 1.0)
            # Touched-rows pricing only applies when the lowering actually
            # takes the sparse path: PS + vocab(axis-0) partitioning
            # (lowering.py make_plan's sparse_lookup gate).
            sparse_fast = (
                node.is_sparse and sync.kind == "ps" and sharded
                and node.partitioner.num_shards > 1
                and max(node.partitioner.split_axis, 0) == 0)

            if sparse_fast:
                # Sparse sharded path: only touched rows move (gather of
                # params + scatter of grads), ≙ the reference's sparse
                # PS push/pull (ps_synchronizer.py:476-535).  The cross-
                # slice share of the shard owners is priced at DCN.
                sp = 2.0 * self.sparsity_fraction * bytes_
                comm_bytes += sp * (1.0 - sparse_frac)
                dcn_bytes += sp * sparse_frac
                num_collectives += 2
                if sparse_frac:
                    dcn_colls += 2
                mem_bytes += (bytes_ / n) * (1.0 + self.opt_state_multiplier) \
                    + self.sparsity_fraction * bytes_  # gathered activations
            elif sharded:
                # Sharded-state (PartitionedPS/ZeRO): reduce_scatter grads
                # + all_gather params — ring-equivalent volume, two
                # launches, optimizer state sharded 1/n.
                comm_bytes += ring * bytes_ * factor
                num_collectives += 2
                if dcn_factor:
                    dcn_bytes += dcn_factor * bytes_ * factor
                    dcn_colls += 2
                mem_bytes += bytes_ \
                    + bytes_ * factor \
                    + (bytes_ * self.opt_state_multiplier) / n
            elif sync.kind == "ps":
                # Dense unpartitioned PS ⇒ ZeRO-1 U_FLAT lowering
                # (lowering.py:150-152): params + grads replicated,
                # reduce_scatter grads + all_gather params (ring-equivalent
                # volume), optimizer state sharded 1/n.
                comm_bytes += ring * bytes_
                num_collectives += 2
                if dcn_factor:
                    dcn_bytes += dcn_factor * bytes_
                    dcn_colls += 2
                mem_bytes += 2.0 * bytes_ \
                    + (bytes_ * self.opt_state_multiplier) / n
            else:
                # Replicated DP allreduce: bucketed collectives count once
                # per group (≙ ScopedAllocator merging, runner.py:40-46).
                comm_bytes += ring * bytes_ * factor
                if dcn_factor:
                    dcn_bytes += dcn_factor * bytes_ * factor
                group = getattr(sync, "group", None)
                if group is not None:
                    groups.add(group)
                else:
                    num_collectives += 1
                    if dcn_factor:
                        dcn_colls += 1
                mem_bytes += bytes_ * (2.0 + self.opt_state_multiplier)

        num_collectives += len(groups)
        if dcn_factor:
            dcn_colls += len(groups)
        tokens, act_hint = self._hints(trainable)
        if tokens and act_hint:
            mem_bytes += act_hint * tokens / n
        bw = self.chip.ici_gbps * 1e9  # bytes/s
        comm_time = (comm_bytes / bw if n > 1 else 0.0) \
            + COLLECTIVE_ALPHA * num_collectives * (1 if n > 1 else 0)
        if dcn_bytes and n > 1:
            dcn_time = dcn_bytes / bw_dcn + dcn_alpha * dcn_colls
            comm_time += dcn_time
        hbm = self.chip.hbm_gb * 1e9 * self.hbm_headroom
        return StrategyCost(
            comm_bytes=comm_bytes + dcn_bytes,
            comm_time_s=comm_time,
            num_collectives=num_collectives + dcn_colls,
            mem_bytes_per_device=mem_bytes,
            feasible=mem_bytes <= hbm,
            dcn_bytes=dcn_bytes,
            dcn_time_s=dcn_time,
        )
