"""Parsed-HLO facts layer: everything the program linter reads.

One pass over optimized (post-SPMD-partitioning) HLO text extracts the
structural facts the rules consume — collective ops with their wire
dtypes, every typed array shape, the ENTRY step-boundary signature,
dynamic-update-slice writes, copies, host transfers, optimization
barriers, fused loops, and buffer donation — so a rule is a predicate
over :class:`ProgramFacts`, never a regex of its own.

These helpers began life inside ``tools/hlo_probe.py``'s hand-rolled
probes; they now live here so any lowered program — a training step, a
decode window, any zoo candidate — is checked by the same facts + rules
engine (``tools/hlo_probe.py`` re-exports them unchanged for
back-compat).
"""
from __future__ import annotations

import collections
import dataclasses
import re

# HLO spells ops `%name = type all-reduce(...)`; async TPU lowerings
# split into -start/-done pairs — count the -start as the op.
_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s*"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")

# Every typed array shape in HLO text: `f32[8,8,93]{2,1,0}` etc.
_SHAPE_RE = re.compile(
    r"\b(?:pred|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|"
    r"f8\w*|bf16|f16|f32|f64|c64|c128)\[([0-9,]*)\]")

# Same scan keeping the element type — the quantized-collectives rules
# assert the *dtype* on the wire, not just the op kind.
_TYPED_SHAPE_RE = re.compile(
    r"\b(pred|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|"
    r"f8\w*|bf16|f16|f32|f64|c64|c128)\[([0-9,]*)\]")

# Result-type prefix + collective kind: `%x = f16[8]{0} all-reduce(...)`
# or the tuple/async forms `= (s8[4], s8[4]) all-gather-start(...)`.
_COLLECTIVE_TYPED_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s*"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")

# Wire dtypes a narrowed boundary may carry: bf16 casts, f16 int8-level
# sums, true-s8 gathers (and any future fp8 wire).
_NARROW_DTYPES = ("bf16", "f16", "s8", "u8", "f8")

_CONVERT_RE = re.compile(r"=\s*(\w+)\[[0-9,]*\][^ ]*\s*convert\(")
_DUS_RE = re.compile(r"dynamic-update-slice(?:-start)?\(")
_COPY_RE = re.compile(r"=\s*(?:\([^)]*\)|\S+?\[([0-9,]*)\]\S*)\s*copy\(")

# Host boundary crossings inside a step: send/recv/infeed/outfeed ops
# and the host-offloading annotation custom-calls.  A training or decode
# step should stay device-resident end to end — any of these is a
# per-step host round-trip.
_HOST_TRANSFER_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s*"
    r"(send|recv|infeed|outfeed)(?:-start|-done)?\(")
_HOST_CUSTOM_CALL_RE = re.compile(
    r"custom-call[^\n]*custom_call_target="
    r"\"[^\"]*(MoveToHost|MoveToDevice|PinToHost)[^\"]*\"")

# Optimization barriers (the re-fusion guards the decomposed collective
# pairs and the chained ZeRO-3 gathers lean on).
_BARRIER_RE = re.compile(r"\b(?:opt-barrier|optimization-barrier)(?:\.\d+)?\(")

# Fused-kernel markers: every Pallas kernel call site is wrapped in a
# `jax.named_scope("adtk_<kernel>")` (kernel.pallas.kernel_marker), and
# the scope string survives XLA optimization inside op_name metadata —
# fusion keeps per-instruction metadata — so marker counts ARE evidence
# the kernel's ops exist in the optimized program (the ADT120 rule).
_KERNEL_MARKER_RE = re.compile(r"adtk_([a-z0-9_]+)")

# Plain `gather` ops and their first operand (the paged-KV block-table
# rule scans for gathers whose OPERAND carries the block pool's
# distinctive extent — the structural evidence the decode reads K/V
# through the table).  HLO text names the operand; whether it also
# prints the operand's shape inline (`gather(f32[13,2]{1,0} %pool, ...)`)
# or only the name (`gather(%pool, ...)`, jax 0.9.0) is the printer's
# choice, so the shape is resolved through the operand's defining
# instruction when it is not inline.  The negative lookbehind keeps
# `all-gather(` (a collective, counted above) out.
_ARRAY_TYPE = (r"(?:pred|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|"
               r"f8\w*|bf16|f16|f32|f64|c64|c128)\[([0-9,]*)\]")
_GATHER_RE = re.compile(
    r"(?<![\w-])gather\(\s*(?:" + _ARRAY_TYPE + r"\S*\s+)?%?([\w.\-]+)")
_ARRAY_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*" + _ARRAY_TYPE, re.M)


def collective_counts(hlo_text: str) -> dict[str, int]:
    """Count collective ops by kind in optimized HLO text."""
    counts = collections.Counter(_COLLECTIVE_RE.findall(hlo_text))
    return {k: counts.get(k, 0)
            for k in ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")}


def collective_wire(hlo_text: str) -> list[tuple[str, str, int]]:
    """Every collective op's ``(kind, element_type, result_elements)``
    from optimized HLO text — the wire-dtype analog of
    :func:`collective_counts` (async ``-start`` forms count once; for
    tuple results the widest element drives the entry)."""
    out = []
    for m in _COLLECTIVE_TYPED_RE.finditer(hlo_text):
        prefix, kind = m.group(1), m.group(2)
        best = None
        for dt, dims in _TYPED_SHAPE_RE.findall(prefix):
            elems = 1
            for d in dims.split(","):
                if d:
                    elems *= int(d)
            if best is None or elems > best[1]:
                best = (dt, elems)
        if best is None:
            best = ("", 0)
        out.append((kind, best[0], best[1]))
    return out


def narrowed_collective_counts(hlo_text: str) -> dict[str, int]:
    """Collectives whose wire element type is narrower than fp32, by
    kind — zero everywhere for an fp32-policy program; the policied
    boundaries for a narrowed one."""
    counts: dict[str, int] = {
        k: 0 for k in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")}
    for kind, dtype, _ in collective_wire(hlo_text):
        if any(dtype.startswith(n) for n in _NARROW_DTYPES):
            counts[kind] += 1
    return counts


def nonscalar_all_reduces(hlo_text: str) -> int:
    """All-reduce ops with a result of more than one element: the
    shared-scale pmaxes a quantized boundary adds are scalars, so this
    count isolates the payload-carrying reductions — a monolithic
    model-axis all-reduce surviving (or re-fusing after) a decomposition
    shows up here."""
    return sum(1 for kind, _, elems in collective_wire(hlo_text)
               if kind == "all-reduce" and elems > 1)


def convert_counts(hlo_text: str) -> dict[str, int]:
    """Count ``convert`` ops by result element type — the
    convert-before/convert-after halves of a narrowed boundary."""
    return dict(collections.Counter(_CONVERT_RE.findall(hlo_text)))


def buffers_with_dim(hlo_text: str, dim: int) -> int:
    """Count array shapes carrying ``dim`` in optimized HLO text — the
    memory-shape analog of :func:`collective_counts`: with a dim chosen
    to be distinctive (a vocab size no other tensor dimension equals),
    zero hits proves the program never materializes a buffer of that
    extent on any device."""
    hits = 0
    for m in _SHAPE_RE.finditer(hlo_text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if dim in dims:
            hits += 1
    return hits


def buffers_with_dim_repeated(hlo_text: str, dim: int,
                              times: int = 2) -> int:
    """Count array shapes carrying ``dim`` at least ``times`` times —
    e.g. a ``[.., T, T]`` attention-score square at a distinctive
    sequence extent, which a single-token decode step must never
    build."""
    hits = 0
    for m in _SHAPE_RE.finditer(hlo_text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if dims.count(dim) >= times:
            hits += 1
    return hits


def dynamic_update_slices(hlo_text: str) -> int:
    """Count dynamic-update-slice ops (fused or top-level)."""
    return len(_DUS_RE.findall(hlo_text))


def large_copies_with_dim(hlo_text: str, dim: int, min_volume: int) -> int:
    """Count ``copy`` ops whose result shape carries ``dim`` AND at
    least ``min_volume`` elements — the signature of a full-cache
    round-trip (small layout copies of token-shaped slices pass)."""
    hits = 0
    for m in _COPY_RE.finditer(hlo_text):
        if m.group(1) is None:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        vol = 1
        for d in dims:
            vol *= d
        if dim in dims and vol >= min_volume:
            hits += 1
    return hits


def gathers_with_operand_dim(hlo_text: str, dim: int) -> int:
    """Count plain ``gather`` ops whose first operand's shape carries
    ``dim`` — with a dim chosen distinctive (the paged block pool's
    ``num_blocks`` extent), a hit IS a block-table gather over the KV
    pool, and zero hits proves the program never reads the cache
    through the table."""
    shape_of = None
    hits = 0
    for m in _GATHER_RE.finditer(hlo_text):
        dims = m.group(1)
        if dims is None:                  # operand printed by name only
            if shape_of is None:
                shape_of = dict(_ARRAY_DEF_RE.findall(hlo_text))
            dims = shape_of.get(m.group(2), "")
        if dim in [int(d) for d in dims.split(",") if d]:
            hits += 1
    return hits


def host_transfers(hlo_text: str) -> int:
    """Count host boundary crossings (send/recv/infeed/outfeed and
    host-offloading custom-calls; ``-start``/``-done`` pairs count per
    half the same way everywhere, so zero stays zero)."""
    return (len(_HOST_TRANSFER_RE.findall(hlo_text))
            + len(_HOST_CUSTOM_CALL_RE.findall(hlo_text)))


def optimization_barriers(hlo_text: str) -> int:
    """Count optimization-barrier ops (the re-fusion guards)."""
    return len(_BARRIER_RE.findall(hlo_text))


def kernel_markers(hlo_text: str) -> dict[str, int]:
    """Occurrences of each fused-kernel ``adtk_<name>`` scope marker in
    op metadata — zero for a kernel means no op of that Pallas kernel
    survived into the program."""
    return dict(collections.Counter(_KERNEL_MARKER_RE.findall(hlo_text)))


def entry_signature(hlo_text: str) -> str:
    """The ENTRY computation's definition line — every array that is
    live ACROSS the step boundary (donated-in state, fed batch/rng,
    returned state/metrics) appears in this signature; per-layer
    gathers and other step-internal temporaries do not."""
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            return line
    raise ValueError("no ENTRY computation in HLO text")


def has_fused_loop(hlo_text: str) -> bool:
    """A ``while`` op is present: the k-step / K-token window lowered
    as ONE fused loop dispatch, not an unrolled (or per-step) series."""
    return " while(" in hlo_text or "while (" in hlo_text


def has_io_alias(hlo_text: str) -> bool:
    """The module declares input/output aliasing — donated state is
    updated in place instead of re-allocated per dispatch."""
    return "input_output_alias" in hlo_text


@dataclasses.dataclass(frozen=True)
class ProgramFacts:
    """Every structural fact program-lint rules consume, extracted once
    from an optimized HLO module's text."""

    text: str
    collectives: tuple          # ((kind, dtype, elems), ...)
    counts: dict                # kind -> count
    narrowed: dict              # kind -> narrower-than-fp32 count
    converts: dict              # result dtype -> convert count
    dus: int
    host_transfers: int
    barriers: int
    fused_loop: bool
    io_alias: bool
    entry: str                  # ENTRY line, "" when absent
    markers: dict = dataclasses.field(default_factory=dict)
    # fused-kernel marker name -> occurrence count

    @classmethod
    def from_hlo(cls, hlo_text: str) -> "ProgramFacts":
        try:
            entry = entry_signature(hlo_text)
        except ValueError:
            entry = ""
        return cls(
            text=hlo_text,
            collectives=tuple(collective_wire(hlo_text)),
            counts=collective_counts(hlo_text),
            narrowed=narrowed_collective_counts(hlo_text),
            converts=convert_counts(hlo_text),
            dus=dynamic_update_slices(hlo_text),
            host_transfers=host_transfers(hlo_text),
            barriers=optimization_barriers(hlo_text),
            fused_loop=has_fused_loop(hlo_text),
            io_alias=has_io_alias(hlo_text),
            entry=entry,
            markers=kernel_markers(hlo_text),
        )

    # Shape scans stay methods (they take the dim parameter, so they
    # cannot be precomputed into fields).
    def buffers_with_dim(self, dim: int) -> int:
        return buffers_with_dim(self.text, dim)

    def buffers_with_dim_repeated(self, dim: int, times: int = 2) -> int:
        return buffers_with_dim_repeated(self.text, dim, times)

    def large_copies_with_dim(self, dim: int, min_volume: int) -> int:
        return large_copies_with_dim(self.text, dim, min_volume)

    def buffers_with_dims(self, dims) -> int:
        """Array shapes carrying ALL of ``dims`` at once — e.g. the
        dense KV cache's ``[.., slots, .., max_len, ..]`` lane shape at
        two distinctive extents, which a paged program must never
        build."""
        dims = list(dims)
        hits = 0
        for m in _SHAPE_RE.finditer(self.text):
            got = [int(d) for d in m.group(1).split(",") if d]
            if all(d in got for d in dims):
                hits += 1
        return hits

    def gathers_with_operand_dim(self, dim: int) -> int:
        return gathers_with_operand_dim(self.text, dim)

    def boundary_buffers_with_dim(self, dim: int) -> int:
        """Step-boundary (ENTRY signature) buffers carrying ``dim``."""
        return buffers_with_dim(self.entry, dim) if self.entry else 0

    def payload_all_reduces(self) -> int:
        return sum(1 for kind, _, elems in self.collectives
                   if kind == "all-reduce" and elems > 1)

    def gathers_larger_than(self, max_elems: int) -> int:
        """All-gather ops whose result exceeds ``max_elems`` — the
        full-array-gather scan."""
        return sum(1 for kind, _, elems in self.collectives
                   if kind == "all-gather" and elems > max_elems)


def compiled_text(jitted, *args) -> str:
    """Optimized (post-SPMD-partitioning) HLO of one jitted program."""
    return jitted.lower(*args).compile().as_text()
