"""Strategy IR: the serializable distribution strategy.

TPU-native counterpart of the reference's protobuf strategy schema
(``autodist/proto/strategy.proto:30-69`` and
``autodist/proto/synchronizers.proto:25-57``) and its Python wrapper
(``autodist/strategy/base.py:28-99``).  A Strategy is a per-variable list of
node configs — synchronizer choice plus optional partitioning — together
with a graph-level config (replica count ≙ data-axis size, mesh axes).

Design differences from the reference, on purpose:

* Serialization is JSON (the reference used protobuf purely as a
  file-serializable IR; JSON keeps the same chief-builds/workers-load flow
  with zero codegen).
* ``partitioner`` is still the reference's `"1,4,1"` axis-split string
  (``partitioner.py:38-150``), but it now resolves to a mesh-axis
  assignment (GSPMD ``PartitionSpec``) instead of graph surgery.
* Synchronizers describe *collective lowering* (psum / reduce-scatter /
  all-gather patterns over ICI) instead of graph-rewrite kernels.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Optional

from autodist_tpu import const


# --------------------------------------------------------------------------- #
# Per-collective precision policy (PR 8, EQuARX-style: PAPERS.md
# 2506.17615).  Every collective *boundary class* a lowering emits gets
# one policy slot; the slot's value is the wire precision the boundary's
# payload narrows to (summing collectives carry int8 levels on an fp16
# wire; gathers carry a true s8 wire — kernel/quantize.py).  An absent
# policy (the empty dict — what every pre-PR-8 strategy JSON
# deserializes to) is fp32 everywhere: today's exact behavior.
# --------------------------------------------------------------------------- #
from autodist_tpu.kernel.quantize import (PRECISIONS,  # noqa: E402
                                          UnknownPrecisionError)

# --------------------------------------------------------------------------- #
# Fused-kernel tier (PR 13): the Strategy IR's ``kernel`` slot elects
# Pallas kernels from :data:`~autodist_tpu.kernel.pallas.KERNEL_CHOICES`
# in place of their composed-XLA-op lowerings — a per-topology cost-model
# decision beside ``comm_overlap``/``precision``, never an unconditional
# swap.  An absent slot (the empty dict — what every pre-PR-13 strategy
# JSON deserializes to) is the composed lowering everywhere.
# --------------------------------------------------------------------------- #
from autodist_tpu.kernel.pallas import (KERNEL_CHOICES,  # noqa: E402
                                        OBSERVED_KERNELS)


class UnknownKernelError(ValueError):
    """A kernel name outside :data:`~autodist_tpu.kernel.pallas
    .KERNEL_CHOICES` — the named error a hand-edited strategy JSON gets
    instead of a silently ignored election."""


def normalize_kernel(policy) -> dict:
    """Canonicalize a fused-kernel election.

    ``None``/``{}``/``False``/``""`` -> ``{}`` (composed lowerings —
    the pre-PR-13 behavior); ``True``/``"all"`` elects every kernel; a
    bare name or an iterable of names elects those; a dict keeps only
    truthy entries.  The canonical form maps each elected name to
    ``True`` so pre-PR-13 JSON round-trips with the slot absent-or-empty
    and hand edits stay readable.  Unknown names raise
    :class:`UnknownKernelError`.

    One exception to "truthy only": a kernel its call site elects from
    what it observes (:data:`~autodist_tpu.kernel.pallas
    .OBSERVED_KERNELS`) keeps an explicit ``False`` — the word that
    forbids it — since absence there means "left to the call site".
    """
    if policy in (None, False, "", {}, (), []):
        return {}
    if policy is True or policy == "all":
        return {k: True for k in KERNEL_CHOICES}
    if isinstance(policy, str):
        policy = (policy,)
    forbidden = ()
    if isinstance(policy, dict):
        names = [k for k, v in policy.items() if v]
        forbidden = [k for k, v in policy.items()
                     if v is False and k in OBSERVED_KERNELS]
    elif isinstance(policy, (list, tuple, set, frozenset)):
        names = list(policy)
    else:
        raise UnknownKernelError(
            f"kernel election must be a name, an iterable of names, or "
            f"a name->bool dict; got {type(policy).__name__}")
    out = {}
    for name in names:
        if name not in KERNEL_CHOICES:
            raise UnknownKernelError(
                f"unknown kernel {name!r}; expected one of "
                f"{list(KERNEL_CHOICES)}")
        out[name] = True
    out.update(dict.fromkeys(forbidden, False))
    return {k: out[k] for k in KERNEL_CHOICES if k in out}


# --------------------------------------------------------------------------- #
# Serving KV-cache layout (PR 14): the ``parallel`` dict's serving knob.
# ``"dense"`` reserves one [max_len] lane per batch slot (the pre-PR-14
# behavior, what every earlier strategy JSON deserializes to);
# ``"paged"`` elects the block-paged pool + block-table layout
# (serving/kv_cache.py PagedKVCache), admitted against free blocks —
# the capacity side the cost model's decode objective prices.
# --------------------------------------------------------------------------- #
KV_LAYOUTS = ("dense", "paged")


class UnknownKVLayoutError(ValueError):
    """A kv_layout outside :data:`KV_LAYOUTS` — the named error a
    hand-edited strategy JSON (or engine kwarg) gets instead of a
    silently dense cache."""


def normalize_kv_layout(value) -> str:
    """Canonicalize the serving KV-cache layout knob.  ``None``/``""``
    -> ``"dense"`` (every pre-PR-14 strategy); unknown names raise
    :class:`UnknownKVLayoutError`."""
    if value in (None, ""):
        return "dense"
    if value not in KV_LAYOUTS:
        raise UnknownKVLayoutError(
            f"unknown kv_layout {value!r}; expected one of "
            f"{list(KV_LAYOUTS)}")
    return str(value)


# --------------------------------------------------------------------------- #
# Serving throughput ladder (PR 16): three more ``parallel``-dict knobs,
# each normalized here and seeded into the engine by
# ``seed_engine_kwargs`` exactly like ``kv_layout``.  All three default
# to OFF, which is what every pre-PR-16 strategy JSON deserializes to —
# the absent-key form keeps earlier JSON byte-stable.
# --------------------------------------------------------------------------- #
def normalize_prefill_chunk(value):
    """Canonicalize the chunked-prefill knob: ``None``/``0``/``False``
    -> ``None`` (single-shot prefill, the pre-PR-16 behavior); a
    positive int is the chunk length in tokens (the engine additionally
    requires a ``kv_block_len`` multiple so chunk writes stay
    block-granular).  Anything else raises ``ValueError``."""
    if value in (None, 0, False, ""):
        return None
    if isinstance(value, bool) or not isinstance(value, int) or value < 1:
        raise ValueError(
            f"prefill_chunk must be None or a positive int (tokens per "
            f"prefill chunk); got {value!r}")
    return int(value)


def normalize_prefix_caching(value) -> bool:
    """Canonicalize the prefix-caching knob: truthy -> ``True`` (the
    refcounted copy-on-write block allocator shares prompt-prefix
    blocks), anything falsy -> ``False`` (pre-PR-16).  Requires the
    paged layout — the engine validates, plan lint reports."""
    return bool(value)


def normalize_speculative(value):
    """Canonicalize the speculative-decoding knob: ``None``/``0``/
    ``False`` -> ``None`` (vanilla decode); a positive int is ``k``,
    the number of draft tokens proposed per target verify step.
    Anything else raises ``ValueError``."""
    if value in (None, 0, False, ""):
        return None
    if isinstance(value, bool) or not isinstance(value, int) or value < 1:
        raise ValueError(
            f"speculative must be None or a positive int (draft tokens "
            f"per verify step); got {value!r}")
    return int(value)


PRECISION_BOUNDARIES = (
    # dp gradient sync (all-reduce / reduce-scatter).  Realized through
    # the compressor machinery — the one boundary with persistent error-
    # feedback state — so "bf16"/"int8" here elect the EF compressors.
    "grad",
    # TP activation psums (Megatron row/column boundaries, forward AND
    # their custom-VJP backward), including the decomposed rs+ag halves
    # and the vocab-parallel prologue lookup psum.
    "tp_psum",
    # Vocab-parallel epilogue statistics: the pmax/psum token-shaped
    # stats and the backward hidden-state cotangent psum.
    "vocab_stats",
    # ZeRO-3 on-demand parameter gathers (forward all-gather) and their
    # custom-VJP backward cotangent reduce-scatter.
    "zero3_gather",
    # MoE dispatch/combine all-to-alls over the expert axis (forward AND
    # backward; permute-shaped, so the wire narrows like a gather — a
    # true s8 wire, no level-headroom bit).
    "moe_a2a",
)

# Wire bits per precision (telemetry gauges / the report schema gate).
PRECISION_BITS = {"fp32": 32, "bf16": 16, "int8": 8}


def normalize_precision(policy) -> dict:
    """Canonicalize a per-collective precision request.

    ``None``/``{}``/``"fp32"`` -> ``{}`` (fp32 everywhere — the
    pre-PR-8 behavior); a bare string applies one precision to every
    boundary class; a dict maps boundary -> precision (unnamed
    boundaries stay fp32).  Explicit ``"fp32"`` entries are dropped so
    the canonical form is minimal and pre-PR-8 JSON round-trips
    byte-stable.  Unknown boundaries/values raise
    :class:`UnknownPrecisionError`.
    """
    if policy in (None, "", "fp32"):
        return {}
    if isinstance(policy, str):
        if policy not in PRECISIONS:
            raise UnknownPrecisionError(
                f"unknown collective precision {policy!r}; expected one "
                f"of {list(PRECISIONS)}")
        return {b: policy for b in PRECISION_BOUNDARIES}
    if not isinstance(policy, dict):
        raise UnknownPrecisionError(
            f"collective precision must be a string or a per-boundary "
            f"dict, got {type(policy).__name__}")
    out = {}
    for boundary, value in policy.items():
        if boundary not in PRECISION_BOUNDARIES:
            raise UnknownPrecisionError(
                f"unknown collective boundary {boundary!r}; expected one "
                f"of {list(PRECISION_BOUNDARIES)}")
        if value not in PRECISIONS:
            raise UnknownPrecisionError(
                f"{boundary}: unknown precision {value!r}; expected one "
                f"of {list(PRECISIONS)}")
        if value != "fp32":
            out[boundary] = value
    return out


# --------------------------------------------------------------------------- #
# Synchronizer configs (≙ reference synchronizers.proto:25-57)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class AllReduceSynchronizer:
    """Dense gradient allreduce over the data axis.

    ≙ reference ``AllReduceSynchronizer{spec, compressor, group}``
    (``synchronizers.proto:44-57``).  ``spec`` (NCCL/RING/AUTO) becomes the
    ICI fabric — XLA chooses the algorithm — so only compressor and
    bucketing (``group`` ≙ ScopedAllocator merge group,
    ``all_reduce_strategy.py:61-67``) survive as knobs.
    """

    kind: str = "allreduce"
    compressor: str = "none"     # none | fp16 | bf16 | fp16_ef | bf16_ef
                                 # | int8_ef | int8_ring | powersgd[:rank]
    group: int = 0               # bucket id for flatten-concat merging

    def to_dict(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PSSynchronizer:
    """Sharded-state synchronization (parameter-server semantics on TPU).

    ≙ reference ``PSSynchronizer{reduction_destination, local_replication,
    sync, staleness}`` (``synchronizers.proto:25-42``).  On TPU the "PS
    device" becomes a *shard* of the data axis: gradients are
    reduce-scattered (each device owns 1/N of the flattened gradient ≙ the
    accumulator on the PS, ``ps_synchronizer.py:556-633``), the optimizer
    update runs on the owned shard (≙ apply op on the PS device), and
    updated parameters are all-gathered (≙ workers pulling new values /
    proxy refresh, ``proxy_variable.py:96-114``).  The sync barrier token
    queues (``ps_synchronizer.py:335-385``) are implicit in SPMD lockstep.

    ``staleness > 0`` (SSP, ``ps_synchronizer.py:387-458``) fundamentally
    fights SPMD lockstep; it is accepted in the IR and surfaced as a
    documented host-coordination extension (SURVEY.md §5.7 / §7).

    ``zero_stage`` extends the PS semantics along the classic weight-
    update-sharding ladder (arxiv 2004.13336):

    * ``1`` — optimizer state sharded (the U_FLAT scheme above; the
      default, and what every pre-stage strategy JSON deserializes to);
    * ``2`` — gradients live sharded too.  The U_FLAT lowering already
      reduce-scatters instead of all-reducing, so stages 1 and 2 emit
      the same program; the stage is the *accounting* record — the cost
      model charges the gradient term at 1/n only for stage >= 2.
    * ``3`` — the parameter itself is *stored* sharded over the replica
      axes and all-gathered on demand per layer inside the step (the
      gathers are step-internal temporaries; nothing full-sized lives
      across the step boundary).
    """

    kind: str = "ps"
    reduction_destination: str = ""   # informational shard tag; "" = flat uniform
    local_replication: bool = False   # ≙ proxy variable; TPU: params re-gathered anyway
    sync: bool = True
    staleness: int = 0
    zero_stage: int = 1

    def to_dict(self):
        return dataclasses.asdict(self)


SYNCHRONIZER_TYPES = {
    "allreduce": AllReduceSynchronizer,
    "ps": PSSynchronizer,
}


def synchronizer_from_dict(d: dict):
    d = dict(d)
    cls = SYNCHRONIZER_TYPES[d.get("kind", "allreduce")]
    return cls(**d)


# --------------------------------------------------------------------------- #
# Partitioner config (≙ reference PartitionerConfig, partitioner.py:38-150)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class PartitionerConfig:
    """Axis-split spec for one variable.

    ``partition_str`` keeps the reference's `"1,4,1"` format — a
    num-splits per dimension list, single split axis (the reference's
    single-axis constraint, ``partitioner.py:126-150``).  ``mesh_axis``
    names the mesh axis the split maps onto (default: data axis —
    PS-partitioning in the reference spread shards over PS *devices*; the
    TPU analog spreads them over the mesh).
    """

    partition_str: str = ""
    mesh_axis: str = const.DATA_AXIS
    # GSPMD generalization (beyond the reference's single axis): one mesh
    # axis name (or None) per tensor dimension, e.g. ["data", None, "model"].
    # When set it overrides partition_str/mesh_axis and may shard several
    # dimensions — the strategy.proto:40-42 extensibility the reference
    # anticipated.
    spec: Optional[list] = None
    # Latency-hiding lowering of this variable's model-axis activation
    # collective (tensor-parallel layers only): None — blocking psum;
    # "rsag" — reduce-scatter + all-gather pair; "matmul" — chunked
    # collective-matmul ppermute ring (per-hop transfer hides behind
    # per-chunk compute).  Recorded per variable so the cost model can
    # price overlapped layers as max(comm, compute) instead of
    # comm + compute, and so a hand-edited strategy can convert layers
    # selectively.
    comm_overlap: Optional[str] = None
    # Wire precision of this variable's model-axis activation collective
    # (tensor-parallel layers / the vocab-sharded table) — the per-
    # variable record of the graph-level precision policy's tp_psum /
    # vocab_stats slot, mirroring comm_overlap: the cost model prices
    # each boundary from it, and a hand-edited strategy stays readable.
    # None = fp32 (today's exact psum).
    precision: Optional[str] = None

    @property
    def partition_list(self) -> list[int]:
        if not self.partition_str:
            return []
        return [int(x) for x in self.partition_str.split(",")]

    @property
    def split_axis(self) -> int:
        """The single partitioned dimension (reference partitioner.py:139-150)."""
        pl = self.partition_list
        axes = [i for i, n in enumerate(pl) if n > 1]
        if len(axes) > 1:
            raise ValueError(
                f"single-axis partitioning only (got {self.partition_str!r})")
        return axes[0] if axes else -1

    @property
    def num_shards(self) -> int:
        pl = self.partition_list
        return max(pl) if pl else 1

    def to_dict(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d):
        prec = d.get("precision")
        if prec is not None and prec not in PRECISIONS:
            raise UnknownPrecisionError(
                f"partitioner precision {prec!r}: expected one of "
                f"{list(PRECISIONS)} (or null)")
        return cls(**d)


# --------------------------------------------------------------------------- #
# Node / graph / strategy (≙ reference strategy.proto:30-69)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class NodeConfig:
    """Per-variable distribution choice (≙ ``strategy.proto Node``)."""

    var_name: str
    synchronizer: AllReduceSynchronizer | PSSynchronizer = dataclasses.field(
        default_factory=AllReduceSynchronizer)
    partitioner: Optional[PartitionerConfig] = None
    is_sparse: bool = False   # sparse/embedding path (≙ IndexedSlices grads)

    def to_dict(self):
        return {
            "var_name": self.var_name,
            "synchronizer": self.synchronizer.to_dict(),
            "partitioner": self.partitioner.to_dict() if self.partitioner else None,
            "is_sparse": self.is_sparse,
        }

    @classmethod
    def from_dict(cls, d):
        return cls(
            var_name=d["var_name"],
            synchronizer=synchronizer_from_dict(d["synchronizer"]),
            partitioner=(PartitionerConfig.from_dict(d["partitioner"])
                         if d.get("partitioner") else None),
            is_sparse=d.get("is_sparse", False),
        )


@dataclasses.dataclass
class GraphConfig:
    """Graph-level config (≙ ``strategy.proto GraphConfig.replicas``).

    ``replicas`` is the data-parallel degree; ``mesh_axes`` records any
    additional model/seq/pipe/expert axis sizes the strategy assumes.
    """

    replicas: int = 1
    mesh_axes: dict[str, int] = dataclasses.field(default_factory=dict)
    # Lowering path: "collective" = explicit per-variable collectives inside
    # one shard_map (the synchronizer semantics of the reference);
    # "gspmd" = jit + NamedSharding annotations, XLA inserts collectives
    # (for tensor/model-parallel and mixed-axis strategies);
    # "sequence" | "pipeline" | "expert" = the advanced-parallelism
    # lowerings (ring-attention sequence parallel, microbatched pipeline,
    # MoE expert parallel) — the strategy.proto:40-42 extension point the
    # reference anticipated, realized as first-class serializable
    # strategies.
    lowering: str = "collective"
    # Gradient accumulation: each step scans over this many microbatches
    # before the (single) synchronization + optimizer update, trading
    # step latency for global batch sizes that exceed device memory.
    # Composes with the pipeline lowering: each accumulation slice runs
    # the full microbatched pipeline schedule (accum_steps outer scans x
    # parallel.num_microbatches pipeline ticks per optimizer update).
    accum_steps: int = 1
    # Knobs of the advanced-parallelism lowerings, JSON-serializable:
    #   sequence: {"seq_leaves": ["x", "y"]}
    #   pipeline: {"num_microbatches": 4}
    #   expert:   {} (no lowering knobs; routing capacity lives at the
    #   model's expert_parallel_ffn call)
    parallel: dict = dataclasses.field(default_factory=dict)
    # Per-collective precision policy: boundary class -> wire precision
    # (see PRECISION_BOUNDARIES / normalize_precision above).  Empty —
    # what every pre-PR-8 strategy JSON deserializes to — is fp32
    # everywhere; hand-edited unknown boundaries/values are rejected
    # with UnknownPrecisionError at deserialization.
    precision: dict = dataclasses.field(default_factory=dict)
    # Fused-kernel tier election: kernel name -> True (see
    # normalize_kernel above).  Empty — what every pre-PR-13 strategy
    # JSON deserializes to — is the composed lowering everywhere;
    # hand-edited unknown names are rejected with UnknownKernelError at
    # deserialization.
    kernel: dict = dataclasses.field(default_factory=dict)

    def to_dict(self):
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d):
        return cls(replicas=d.get("replicas", 1),
                   mesh_axes=dict(d.get("mesh_axes", {})),
                   lowering=d.get("lowering", "collective"),
                   accum_steps=d.get("accum_steps", 1),
                   parallel=dict(d.get("parallel", {})),
                   precision=normalize_precision(d.get("precision")),
                   kernel=normalize_kernel(d.get("kernel")))


@dataclasses.dataclass
class Strategy:
    """The full serializable strategy (≙ reference ``Strategy`` wrapper,
    ``strategy/base.py:28-99``: ID'd, file-serializable, pretty-printable).
    """

    node_configs: list[NodeConfig] = dataclasses.field(default_factory=list)
    graph_config: GraphConfig = dataclasses.field(default_factory=GraphConfig)
    id: str = ""

    def __post_init__(self):
        if not self.id:
            self.id = self._gen_id()

    def _gen_id(self) -> str:
        h = hashlib.md5(json.dumps(
            [n.to_dict() for n in self.node_configs], sort_keys=True
        ).encode()).hexdigest()[:12]
        return f"{time.strftime('%Y%m%dT%H%M%S')}-{h}"

    def node_config_for(self, var_name: str) -> Optional[NodeConfig]:
        for n in self.node_configs:
            if n.var_name == var_name:
                return n
        return None

    # -- serialization (≙ strategy/base.py:78-99 serialize/deserialize) ---- #
    def to_json(self) -> str:
        return json.dumps({
            "id": self.id,
            "node_configs": [n.to_dict() for n in self.node_configs],
            "graph_config": self.graph_config.to_dict(),
        }, indent=1)

    @classmethod
    def from_json(cls, s: str) -> "Strategy":
        d = json.loads(s)
        return cls(
            id=d["id"],
            node_configs=[NodeConfig.from_dict(n) for n in d["node_configs"]],
            graph_config=GraphConfig.from_dict(d["graph_config"]),
        )

    def serialize(self, path: Optional[str] = None) -> str:
        path = path or os.path.join(const.DEFAULT_STRATEGY_DIR, self.id)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_json())
        return path

    @classmethod
    def deserialize(cls, strategy_id: str, path: Optional[str] = None) -> "Strategy":
        path = path or os.path.join(const.DEFAULT_STRATEGY_DIR, strategy_id)
        with open(path) as f:
            return cls.from_json(f.read())

    def __str__(self):
        gc = self.graph_config
        head = f"Strategy(id={self.id}, replicas={gc.replicas}"
        if gc.lowering != "collective":
            head += f", lowering={gc.lowering}"
        if gc.parallel:
            head += f", parallel={gc.parallel}"
        if gc.precision:
            head += f", precision={gc.precision}"
        if gc.kernel:
            head += ", kernel=" + str(sorted(
                k if v else f"no {k}" for k, v in gc.kernel.items()))
        if gc.accum_steps > 1:
            head += f", accum_steps={gc.accum_steps}"
        lines = [head + ")"]
        for n in self.node_configs:
            part = "-"
            if n.partitioner:
                part = (str(n.partitioner.spec) if n.partitioner.spec
                        else n.partitioner.partition_str)
                if n.partitioner.comm_overlap:
                    part += f" overlap={n.partitioner.comm_overlap}"
            detail = getattr(n.synchronizer, "compressor", "")
            if n.synchronizer.kind == "ps":
                detail = f"zero{getattr(n.synchronizer, 'zero_stage', 1)}"
            lines.append(
                f"  {n.var_name}: sync={n.synchronizer.kind}"
                f"({detail}) part={part}"
                f"{' sparse' if n.is_sparse else ''}")
        return "\n".join(lines)
