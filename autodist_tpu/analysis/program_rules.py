"""Program lint: declarative rules over the parsed-HLO facts layer.

A :class:`Rule` is a named, coded predicate over
:class:`~autodist_tpu.analysis.facts.ProgramFacts` — the declarative
refactor of ``tools/hlo_probe.py``'s hand-rolled probe asserts, so ANY
lowered program (a training step, a decode window, any AutoStrategy zoo
candidate) is checked by the same engine, and new structural contracts
are one factory call, not a new probe function.

Two ways to build a rule set:

* the factories below, composed by hand (what the probes do — they know
  their program's exact geometry and baselines);
* :func:`rules_for_strategy` / :func:`rules_for_decode`, which derive
  the baseline-free contract a program must satisfy from its Strategy
  IR alone (what the zoo sweep does — it has no sibling baseline
  program to compare against).

Every rule carries a stable ``ADT1xx`` diagnostic code
(:mod:`autodist_tpu.analysis.diagnostics`); the mutation harness
(:mod:`autodist_tpu.analysis.mutations`) proves each shipped rule fires
on a seeded violation.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from autodist_tpu.analysis.diagnostics import (ERROR, Diagnostic,
                                               LintReport)
from autodist_tpu.analysis.facts import ProgramFacts


@dataclasses.dataclass(frozen=True)
class Rule:
    """One structural contract: ``check(facts)`` returns violation
    messages (empty = the program honors the contract)."""

    code: str
    name: str
    description: str
    check: Callable[[ProgramFacts], list]
    fix: str = ""
    severity: str = ERROR

    def evaluate(self, facts: ProgramFacts, where: str = "") -> list:
        return [Diagnostic(code=self.code, message=m, where=where,
                           severity=self.severity, fix=self.fix,
                           rule=self.name)
                for m in self.check(facts)]


def check_program(facts: ProgramFacts, rules, where: str = "") -> LintReport:
    """Evaluate ``rules`` against one program's facts."""
    report = LintReport()
    for rule in rules:
        report.extend(rule.evaluate(facts, where=where))
    return report


def lint_program(hlo_text: str, rules, where: str = "") -> LintReport:
    """Convenience: parse facts and evaluate in one call."""
    return check_program(ProgramFacts.from_hlo(hlo_text), rules,
                         where=where)


def lint_block_trace(events, where: str = "block-trace") -> LintReport:
    """Replay a :class:`~autodist_tpu.serving.kv_cache.BlockAllocator`
    event trace against the copy-on-write sharing contract (the PR-16
    prefix-caching rung's runtime artifact — the serving analog of a
    compiled program, linted by the same diagnostic vocabulary).

    Trace grammar (each event a tuple, first element the kind):

    * ``("alloc", b)`` / ``("share", b)`` / ``("free", b)`` — the
      allocator's own refcount movements;
    * ``("write", b)`` — the engine is about to write K/V positions
      into physical block ``b`` (noted per protected decode span);
    * ``("cow", src, dst)`` — the engine copied shared ``src`` into
      privately-held ``dst`` and redirected its table row.

    Two rules:

    * **ADT116** — a ``write`` lands on a block whose replayed refcount
      is > 1 (a shared prefix written in place: the OTHER holder's
      cached tokens silently change) or 0 (a stale table entry outlives
      its block's release);
    * **ADT117** — a ``free`` or ``share`` on a block whose replayed
      refcount is already 0: the double-free that puts one physical
      block on the free list while a table row still maps it — the
      next admission gets handed memory another request is decoding
      through.
    """
    rc: dict = {}
    out = []
    for i, ev in enumerate(events):
        kind = ev[0]
        b = ev[1] if len(ev) > 1 else None
        if kind == "alloc":
            if rc.get(b, 0) > 0:
                out.append(Diagnostic(
                    "ADT117",
                    f"event {i}: alloc handed out block {b} while its "
                    f"refcount is still {rc[b]} — a prior double-free "
                    "put a live block back on the free list",
                    where=where, rule="block_cow_trace",
                    fix="free exactly once per reference; route every "
                        "release through BlockAllocator.free_one"))
            rc[b] = 1
        elif kind == "share":
            if rc.get(b, 0) < 1:
                out.append(Diagnostic(
                    "ADT117",
                    f"event {i}: share of block {b} which is not live "
                    "(refcount 0) — a prefix-index entry outlived its "
                    "block's release",
                    where=where, rule="block_cow_trace",
                    fix="deregister prefix keys when the last "
                        "reference drops (the _block_keys reverse "
                        "map)"))
            else:
                rc[b] += 1
        elif kind == "free":
            if rc.get(b, 0) < 1:
                out.append(Diagnostic(
                    "ADT117",
                    f"event {i}: free of block {b} whose refcount is "
                    "already 0 — double free (the pool would hand the "
                    "same physical block to two requests)",
                    where=where, rule="block_cow_trace",
                    fix="drop exactly one reference per holder; a "
                        "shared block's LAST holder frees it"))
            else:
                rc[b] -= 1
                if rc[b] == 0:
                    del rc[b]
        elif kind == "write":
            n = rc.get(b, 0)
            if n > 1:
                out.append(Diagnostic(
                    "ADT116",
                    f"event {i}: write to block {b} at refcount {n} "
                    "without copy-on-write — the other "
                    f"{n - 1} holder(s)' cached prefix silently "
                    "changes under them",
                    where=where, rule="block_cow_trace",
                    fix="copy the shared block into a private one and "
                        "redirect the writer's table row before the "
                        "write (kv_cache.PagedLayout.protect)"))
            elif n == 0:
                out.append(Diagnostic(
                    "ADT116",
                    f"event {i}: write to block {b} which is not live "
                    "(refcount 0) — a stale table entry outlived its "
                    "block's release",
                    where=where, rule="block_cow_trace",
                    fix="clear the slot's table row on release_slot "
                        "before the block recycles"))
        # ("cow", src, dst) moves no references: dst was privately
        # alloc'd into the reserve earlier and src's drop is the
        # explicit ("free", src) the engine logs right after.
    return LintReport(out)


# --------------------------------------------------------------------------- #
# Rule factories
# --------------------------------------------------------------------------- #
def no_host_transfer() -> Rule:
    def check(f: ProgramFacts):
        if f.host_transfers:
            return [f"step program crosses the host boundary "
                    f"{f.host_transfers} time(s) (send/recv/infeed/"
                    "outfeed or host-offload custom-call)"]
        return []
    return Rule("ADT101", "no_host_transfer",
                "a step program stays device-resident end to end",
                check,
                fix="keep per-step data on device; host I/O belongs in "
                    "the runner, not the compiled step")


def fused_loop() -> Rule:
    def check(f: ProgramFacts):
        if not f.fused_loop:
            return ["multi-step window lowered without a fused while "
                    "loop — steps are dispatching separately"]
        return []
    return Rule("ADT102", "fused_loop",
                "a k-step/K-token window is ONE while-loop dispatch",
                check,
                fix="scan the step body (run_steps / decode window) "
                    "instead of unrolling")


def donated_alias() -> Rule:
    def check(f: ProgramFacts):
        if not f.io_alias:
            return ["no input/output aliasing — donated state/cache "
                    "buffers are re-allocated every dispatch"]
        return []
    return Rule("ADT103", "donated_alias",
                "donated buffers alias into the outputs",
                check,
                fix="donate the state argument (jit donate_argnums / "
                    "input_output_aliases)")


def no_donated_copy(dim: int, min_volume: int, label: str) -> Rule:
    def check(f: ProgramFacts):
        n = f.large_copies_with_dim(dim, min_volume)
        if n:
            return [f"{n} copy op(s) of {label}-sized buffers "
                    f"(dim {dim}, >= {min_volume} elems) per dispatch — "
                    "the in-place update regressed to copy-on-write"]
        return []
    return Rule("ADT104", "no_donated_copy",
                f"no full-{label} copy per dispatch", check,
                fix="keep updates as dynamic-update-slice on the "
                    "donated buffer's native layout")


def no_buffer_with_dim(dims, label: str) -> Rule:
    dims = tuple(dims)

    def check(f: ProgramFacts):
        leaks = sum(f.buffers_with_dim(d) for d in dims)
        if leaks:
            return [f"{leaks} {label}-sized buffer(s) (dim "
                    f"{'/'.join(map(str, dims))}) materialized — the "
                    "sharded form re-replicated (or an all-gather "
                    "assembled the full array)"]
        return []
    return Rule("ADT105", "no_full_buffer",
                f"no full-{label} buffer anywhere in the program", check,
                fix="keep the boundary in its sharded form (vocab "
                    "primitives / sharded epilogue)")


def sharded_step_boundary(dim: int, label: str = "parameter") -> Rule:
    def check(f: ProgramFacts):
        if not f.entry:
            return ["no ENTRY computation found — cannot scan the "
                    "step boundary"]
        n = f.boundary_buffers_with_dim(dim)
        if n:
            return [f"{n} full-{label} buffer(s) (dim {dim}) live "
                    "across the step boundary — storage must stay "
                    "sharded between steps"]
        return []
    return Rule("ADT106", "sharded_step_boundary",
                f"no full {label} lives across the step boundary", check,
                fix="store the variable as its ZeRO shard; gather "
                    "on demand inside the step (zero3_gather)")


def min_collectives(kind: str, n: int, label: str) -> Rule:
    def check(f: ProgramFacts):
        got = f.counts.get(kind, 0)
        if got < n:
            return [f"{got} {kind} op(s); the plan requires >= {n} "
                    f"({label}) — collapsed into a bulk op or missing"]
        return []
    return Rule("ADT107", f"min_{kind.replace('-', '_')}",
                f">= {n} {kind} ops ({label})", check,
                fix="keep the per-layer chain barrier-linked "
                    "(chain_gathers) so XLA cannot combine it")


def no_refused_pair(baseline_all_reduces: int,
                    payload_only: bool = True) -> Rule:
    """The converted program's all-reduce count must EQUAL the
    baseline's — any excess is a monolithic model-axis all-reduce that
    survived or re-fused, any shortfall means data/pipe sync went
    missing.  ``payload_only`` counts only >1-element results (the
    scalar pmaxes a quantized boundary adds are counted separately)."""
    def check(f: ProgramFacts):
        got = f.payload_all_reduces() if payload_only \
            else f.counts.get("all-reduce", 0)
        if got != baseline_all_reduces:
            kind = "payload-carrying " if payload_only else ""
            return [f"{got} {kind}all-reduce(s) vs the baseline's "
                    f"{baseline_all_reduces} — a monolithic model-axis "
                    "all-reduce survived the decomposition (or XLA "
                    "re-fused the rs+ag pair), or a sync went missing"]
        return []
    return Rule("ADT108", "no_refused_pair",
                "the decomposed rs+ag pair stays un-re-fused", check,
                fix="keep the optimization_barrier between the "
                    "reduce-scatter and all-gather halves")


def quantized_wire(mins: Optional[dict] = None,
                   clean: bool = False) -> Rule:
    """``mins``: kind -> minimum narrowed-collective count the policy
    requires; ``clean=True`` instead asserts ZERO narrowed collectives
    (the fp32-policy program — an un-policied boundary silently
    narrowing fails)."""
    mins = dict(mins or {})

    def check(f: ProgramFacts):
        out = []
        if clean:
            total = sum(f.narrowed.values())
            if total:
                out.append(f"{total} narrowed collective(s) in an "
                           "fp32-policy program — an un-policied "
                           f"boundary silently narrowed: {f.narrowed}")
            return out
        for kind, n in mins.items():
            got = f.narrowed.get(kind, 0)
            if got < n:
                out.append(f"policy narrows the {kind} boundary but "
                           f"only {got} narrowed op(s) found "
                           f"(expected >= {n}) — the lowering dropped "
                           "the precision policy")
        return out
    return Rule("ADT109", "quantized_wire",
                "collective wire dtypes match the declared precision "
                "policy", check,
                fix="route the boundary through precision_scope / "
                    "zero3_gather(precision=) so the policy reaches "
                    "the wire")


def no_full_gather(max_elems: int) -> Rule:
    def check(f: ProgramFacts):
        n = f.gathers_larger_than(max_elems)
        if n:
            return [f"{n} all-gather(s) with results above "
                    f"{max_elems} elements — a full-array "
                    "materialization where the plan promises shards"]
        return []
    return Rule("ADT110", "no_full_gather",
                f"no all-gather result exceeds {max_elems} elements",
                check,
                fix="gather per layer/leaf on demand instead of "
                    "materializing whole arrays")


def min_dus(n: int, label: str = "KV cache") -> Rule:
    def check(f: ProgramFacts):
        if f.dus < n:
            return [f"{f.dus} dynamic-update-slice op(s); expected "
                    f">= {n} ({label} writes) — the in-place write "
                    "lowered to something else (scatter/concat)"]
        return []
    return Rule("ADT111", "min_dus",
                f">= {n} in-place dynamic-update-slice writes ({label})",
                check,
                fix="write through lax.dynamic_update_slice on the "
                    "donated buffer")


def no_score_square(dim: int) -> Rule:
    def check(f: ProgramFacts):
        n = f.buffers_with_dim_repeated(dim)
        if n:
            return [f"{n} [{dim}, {dim}]-extent buffer(s) — a "
                    "full-sequence attention-score square in a "
                    "single-token step"]
        return []
    return Rule("ADT112", "no_score_square",
                f"no [{dim}, {dim}] attention square", check,
                fix="decode attention scores live at [B, heads, 1, T]")


def no_collectives() -> Rule:
    def check(f: ProgramFacts):
        total = sum(f.counts.values())
        if total:
            return [f"single-replica program carries {total} "
                    f"cross-device collective(s): {f.counts}"]
        return []
    return Rule("ADT113", "no_collectives",
                "a 1-device program emits zero collectives", check,
                fix="the single-replica bypass (kernel/lowering.py) "
                    "must skip the sync")


def fused_kernel_replaced(kernels, tp: int = 2, expert: int = 2) -> Rule:
    """ADT120: every elected fused kernel actually replaced its
    composed op soup.  Evidence, per kernel:

    * its ``adtk_<name>`` scope marker appears in op metadata (Pallas
      kernel ops survived into the optimized program — a program built
      from a kernel-slot-dropped sibling strategy has none);
    * ``quant_ring`` additionally shows the EQuARX wire: ``>= 2(tp-1)``
      TRUE-``s8`` collective-permutes (the composed int8 lowering has
      zero — its wire is one monolithic fp16-levels all-reduce);
    * ``collective_matmul`` additionally shows the ring itself:
      ``>= tp-1`` collective-permutes (the blocking sibling has none);
    * ``a2a_ring`` additionally shows the dispatch/combine ring wire:
      ``>= 2(expert-1)`` TRUE-``s8`` collective-permutes per step (one
      (expert-1)-hop shift ring each for dispatch and combine; the
      composed int8 a2a lowers to monolithic s8 ``all-to-all`` ops,
      which contribute zero collective-permutes).
    """
    kernels = tuple(kernels)

    def check(f: ProgramFacts):
        out = []
        for name in kernels:
            if not f.markers.get(name):
                out.append(
                    f"elected kernel {name!r} left no adtk_{name} op in "
                    "the compiled program — the composed lowering "
                    "survived (kernel slot dropped between plan and "
                    "program)")
                continue
            if name == "quant_ring":
                s8_perms = f.narrowed.get("collective-permute", 0)
                want = 2 * (tp - 1)
                if s8_perms < want:
                    out.append(
                        f"quant_ring elected but only {s8_perms} "
                        f"narrowed collective-permute(s) (expected >= "
                        f"{want}) — the s8 ring wire is missing")
            if name == "collective_matmul":
                perms = f.counts.get("collective-permute", 0)
                if perms < tp - 1:
                    out.append(
                        f"collective_matmul elected but only {perms} "
                        f"collective-permute(s) (expected >= {tp - 1}) "
                        "— the chunked ring is missing")
            if name == "a2a_ring":
                s8_perms = f.narrowed.get("collective-permute", 0)
                want = 2 * (expert - 1)
                if s8_perms < want:
                    out.append(
                        f"a2a_ring elected but only {s8_perms} "
                        f"narrowed collective-permute(s) (expected >= "
                        f"{want} for the {expert}-way dispatch/combine "
                        "rings) — the s8 ring wire is missing")
        return out

    return Rule("ADT120", "fused_kernel_replaced",
                "every elected fused kernel replaced its composed ops",
                check,
                fix="thread the Strategy IR kernel slot through the "
                    "lowering (kernel_scope / the engine's flash "
                    "dispatch) so the Pallas call site is reached")


def paged_cache(num_slots: int, max_len: int,
                pool_blocks: Optional[int] = None) -> Rule:
    """ADT115: the paged decode program actually dropped the dense
    reservation.  Two halves of the evidence:

    * ZERO buffers shaped with BOTH the slot count and the ``max_len``
      extent (the dense cache's ``[L, slots, heads, max_len, dh]`` lane
      signature at two distinctive dims) — a hit means the paged
      election compiled the dense layout anyway;
    * ``pool_blocks`` given (the composed, non-flash path): >= 1
      ``gather`` whose operand carries the pool's distinctive
      ``num_blocks`` extent — the block-table read.  The paged *flash*
      program streams blocks inside the Pallas kernel (no HLO gather
      exists to scan), so its table evidence is the ADT120
      ``adtk_flash_decode`` marker instead and ``pool_blocks`` stays
      ``None``.
    """
    def check(f: ProgramFacts):
        out = []
        lanes = f.buffers_with_dims((num_slots, max_len))
        if lanes:
            out.append(
                f"{lanes} dense [{num_slots} x .. x {max_len}]-shaped "
                "cache buffer(s) in a paged decode program — the "
                "kv_layout election compiled the dense per-slot "
                "reservation anyway")
        if pool_blocks is not None:
            got = f.gathers_with_operand_dim(pool_blocks)
            if got < 1:
                out.append(
                    f"no gather over the [{pool_blocks}, ...] block "
                    "pool — the decode reads K/V without the block "
                    "table (dense addressing survived)")
        return out

    return Rule("ADT115", "paged_cache",
                "a paged decode carries no dense cache lane and reads "
                "K/V through the block table", check,
                fix="thread kv_layout='paged' through the engine so "
                    "writes/reads route through PagedKVCache and the "
                    "block table")


def min_extra_all_reduces(baseline: int, n: int, label: str) -> Rule:
    def check(f: ProgramFacts):
        extra = f.counts.get("all-reduce", 0) - baseline
        if extra < n:
            return [f"only {extra} all-reduce(s) over the baseline's "
                    f"{baseline}; expected >= {n} ({label})"]
        return []
    return Rule("ADT114", "min_extra_all_reduces",
                f">= {n} all-reduces over baseline ({label})", check,
                fix="the model-axis boundaries must psum (or their "
                    "decomposed forms must appear)")


# --------------------------------------------------------------------------- #
# Deriving a contract from the Strategy IR (the zoo sweep's entry)
# --------------------------------------------------------------------------- #
def rules_for_strategy(strategy, *, vocab_size: Optional[int] = None,
                       boundary_dim: Optional[int] = None,
                       zero3_min_gathers: int = 1) -> list[Rule]:
    """The baseline-free structural contract a train-step program must
    satisfy, derived from its Strategy IR alone.

    ``vocab_size``: the workload's vocab extent (distinctive), enabling
    the full-vocab-buffer rule for vocab-parallel plans.
    ``boundary_dim``: a distinctive full-parameter dim, enabling the
    ZeRO-3 step-boundary rule.  Baseline-dependent rules (re-fusion,
    tp-adds-all-reduces) need a sibling program's counts and are
    composed by the probes instead.
    """
    from autodist_tpu.strategy.ir import (PSSynchronizer,
                                          normalize_kernel,
                                          normalize_precision)

    gc = strategy.graph_config
    rules = [no_host_transfer()]
    par = gc.parallel or {}
    tp = max(int(par.get("tensor_parallel", 1)), 1)
    precision = normalize_precision(gc.precision)
    kernel = normalize_kernel(getattr(gc, "kernel", None))
    train_kernels = tuple(k for k in ("quant_ring", "collective_matmul",
                                      "a2a_ring")
                          if k in kernel)
    if train_kernels:
        from autodist_tpu import const
        expert_deg = max(int((gc.mesh_axes or {})
                             .get(const.EXPERT_AXIS, 1) or 1), 1)
        rules.append(fused_kernel_replaced(train_kernels, tp=tp,
                                           expert=expert_deg))
    compressors = {getattr(nc.synchronizer, "compressor", "none") or "none"
                   for nc in strategy.node_configs}
    zero_stages = {nc.synchronizer.zero_stage
                   for nc in strategy.node_configs
                   if isinstance(nc.synchronizer, PSSynchronizer)}

    # Wire precision: a plan with no narrowing anywhere must compile to
    # an all-fp32 wire; a narrowed plan must show it on the right kinds.
    narrowing_compressor = any(
        c not in ("none",) and not c.startswith("powersgd")
        for c in compressors)
    if not precision and not narrowing_compressor:
        rules.append(quantized_wire(clean=True))
    else:
        mins = {}
        if tp > 1 and precision.get("tp_psum") \
                and "quant_ring" not in kernel:
            # Under the quant_ring kernel the tp_psum narrowing rides
            # s8 collective-permutes, not narrowed all-reduces — the
            # ADT120 rule above carries that evidence instead.
            mins["all-reduce"] = 1
        if max(zero_stages, default=0) >= 3 \
                and precision.get("zero3_gather"):
            mins["all-gather"] = zero3_min_gathers
        if precision.get("moe_a2a") and "a2a_ring" not in kernel \
                and gc.lowering == "expert" \
                and int((gc.mesh_axes or {}).get("expert", 2) or 2) > 1:
            # Composed narrowed dispatch/combine: the wire is monolithic
            # bf16/s8 all-to-all ops.  Under a2a_ring those become s8
            # collective-permutes and ADT120 carries the evidence.
            mins["all-to-all"] = 1
        if mins:
            rules.append(quantized_wire(mins=mins))

    if tp > 1 and par.get("vocab_parallel") and vocab_size:
        v_pad = vocab_size + (-vocab_size) % tp
        dims = {vocab_size, v_pad}
        rules.append(no_buffer_with_dim(sorted(dims), "vocab"))

    if max(zero_stages, default=0) >= 3:
        rules.append(min_collectives(
            "all-gather", zero3_min_gathers, "per-layer ZeRO-3 gathers"))
        rules.append(min_collectives(
            "reduce-scatter", 1, "ZeRO gradient scatter"))
        if boundary_dim:
            rules.append(sharded_step_boundary(boundary_dim))

    if tp > 1 and par.get("comm_overlap"):
        rules.append(min_collectives(
            "reduce-scatter", 1, "decomposed rs half"))
        rules.append(min_collectives(
            "all-gather", 1, "decomposed ag half"))

    if gc.replicas <= 1 and all(
            v <= 1 for v in (gc.mesh_axes or {}).values()):
        rules.append(no_collectives())
    return rules


def rules_for_reshard(max_shard_elems: int) -> list[Rule]:
    """The structural contract of a compiled reshard program (elastic
    resharding, :mod:`autodist_tpu.elastic.reshard`): redistribution
    must route shard-to-shard through collectives — it must never
    gather a full array (ADT110: no all-gather result beyond the
    largest per-device stored shard, with slack for padding) and never
    stage through the host (ADT101).  This is the memory-efficient
    redistribution claim of arxiv 2112.01075, checked on the optimized
    HLO: peak transfer buffers stay at shard granularity.

    ``max_shard_elems``: the largest per-device stored-shard element
    count across the source and target layouts (see
    ``elastic.reshard.shard_budget``)."""
    return [no_host_transfer(), no_full_gather(max_shard_elems)]


def rules_for_decode(tensor_parallel: int, vocab_parallel: bool, *,
                     vocab_size: int, max_len: int, num_layers: int,
                     num_slots: int, heads_local: int,
                     head_dim: int, kernel=(),
                     kv_layout: str = "dense",
                     pool_blocks: Optional[int] = None) -> list[Rule]:
    """The structural contract of a serving decode window, derived from
    its (tp, vocab_parallel, kernel, kv_layout) config and cache
    geometry."""
    kernel = tuple(kernel)
    rules = [
        no_host_transfer(),
        fused_loop(),
        donated_alias(),
        no_score_square(max_len),
        min_dus(2 * num_layers),
    ]
    if kv_layout == "paged":
        # The paged contract: no dense [slots x max_len] reservation
        # anywhere, and (composed path) the block-table gather over the
        # pool's distinctive extent.  The flash-elected program's table
        # walk lives inside the Pallas kernel — ADT120 carries its
        # evidence — so the gather half is skipped there.
        rules.append(paged_cache(
            num_slots, max_len,
            pool_blocks=None if "flash_decode" in kernel
            else pool_blocks))
    elif "flash_decode" not in kernel:
        # The composed einsum path's no-cache-lane-copy guard.  The
        # flash-elected program is exempt ON CPU ONLY: the Pallas
        # *interpreter* materializes each grid step's operand blocks as
        # copies (on TPU the Mosaic kernel streams the cache via DMA —
        # no HLO copy exists to scan); ADT120 carries the flash
        # program's structural proof instead.
        rules.append(no_donated_copy(
            max_len, num_slots * heads_local * max_len * head_dim,
            "cache-lane"))
    if vocab_parallel and tensor_parallel > 1:
        v_pad = vocab_size + (-vocab_size) % tensor_parallel
        rules.append(no_buffer_with_dim(
            sorted({vocab_size, v_pad}), "vocab"))
        rules.append(min_extra_all_reduces(
            0, 2 * num_layers, "per-layer Megatron boundary psums"))
    if "flash_decode" in kernel:
        rules.append(fused_kernel_replaced(("flash_decode",),
                                           tp=tensor_parallel))
    if tensor_parallel == 1:
        rules.append(no_collectives())
    return rules
