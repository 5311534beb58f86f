"""The shipped program contracts, as probes (``tools/hlo_probe.py``).

Each ``probe_*`` lowers real programs from the memoized corpus
(:mod:`~autodist_tpu.analysis.programs`), evaluates the declarative
rule set that encodes the claim (:mod:`~autodist_tpu.analysis
.program_rules`), raises :class:`AssertionError` on any rule firing
(the probes' historical contract — ``run_probes`` catches it), and
returns the same JSON-able report dict the probe CLI has always
printed.  ``tools/hlo_probe.py`` re-exports these names unchanged.

Plain ``assert`` statements that remain here are *scan-validity
controls* (e.g. "the replicated baseline DOES carry the full-vocab
buffer") — they falsify the probe itself, not the program under test.
"""
from __future__ import annotations

from autodist_tpu.analysis import program_rules as R
from autodist_tpu.analysis import programs
from autodist_tpu.analysis.facts import (ProgramFacts, buffers_with_dim,
                                         collective_counts,
                                         entry_signature,
                                         narrowed_collective_counts,
                                         nonscalar_all_reduces)


def _enforce(text: str, rules, where: str):
    """Evaluate ``rules`` on ``text``; AssertionError on any violation
    (the probe contract: run_probes records it as ``ok: False``)."""
    facts = ProgramFacts.from_hlo(text)
    report = R.check_program(facts, rules, where=where)
    if not report.ok:
        raise AssertionError("; ".join(
            f"[{d.code}] {d.message}" for d in report.errors))
    return facts


def probe_steps_per_loop(k: int = 4) -> dict:
    """k-step ``run_steps`` program == one module, one loop, the
    single-step program's collective counts (not k×: the scan body is
    not unrolled, so steps-per-loop amortizes dispatch, not compute)."""
    text_k, text_1 = programs.tiny_scan_texts(k)
    counts_1 = collective_counts(text_1)
    facts_k = _enforce(text_k, [
        R.fused_loop(),
        R.no_refused_pair(counts_1["all-reduce"], payload_only=False),
    ], f"steps_per_loop[k={k}]")
    counts_k = facts_k.counts
    assert counts_k == counts_1, (
        f"k-step program changed per-kind collective counts: one step "
        f"{counts_1} vs {k} steps {counts_k} — the scan unrolled")
    return {"k": k, "fused_loop": facts_k.fused_loop,
            "collectives_one_step": counts_1,
            "collectives_k_steps": counts_k}


def probe_single_replica() -> dict:
    """1-device program: the allreduce bypass emits ZERO all-reduce ops
    (and no other cross-device collective either)."""
    facts = _enforce(programs.tiny_step_text(1), [R.no_collectives()],
                     "single_replica")
    return {"collectives": facts.counts}


def probe_pipeline_tp() -> dict:
    """tensor_parallel=2 pipeline step: the stage ring's
    collective-permute is present, and the model-axis activation
    all-reduces appear on top of the tp=1 program's count — at least 4
    more (out-proj + wo forward psums, their custom-VJP backward psums),
    emitted once in the tick-scan body."""
    c1 = collective_counts(programs.pipeline_step_text(1))
    _enforce(programs.pipeline_step_text(1), [
        R.min_collectives("collective-permute", 1, "pipeline ring"),
    ], "pipeline_tp[tp=1]")
    facts2 = _enforce(programs.pipeline_step_text(2), [
        R.min_collectives("collective-permute", 1, "pipeline ring"),
        R.min_extra_all_reduces(
            c1["all-reduce"], 4,
            "per-stage Megatron activation all-reduces"),
    ], "pipeline_tp[tp=2]")
    c2 = facts2.counts
    return {"collectives_tp1": c1, "collectives_tp2": c2,
            "model_axis_all_reduces": c2["all-reduce"] - c1["all-reduce"]}


def probe_collective_matmul() -> dict:
    """The latency-hiding decomposition (``Pipeline(comm_overlap=...)``)
    at tp=2, against two baselines: the blocking tp=2 program (whose
    model-axis all-reduces must vanish) and the tp=1 program (whose
    all-reduce count the converted program must *equal* — any excess is
    a monolithic model-axis all-reduce that survived or re-fused, any
    shortfall means data/pipe sync went missing).  The ``"matmul"``
    mode must add ≥ tp−1 collective-permute over blocking tp=2 (the
    chunked ring); both modes must emit reduce-scatter + all-gather
    (the decomposed boundary reductions)."""
    tp = 2
    c1 = collective_counts(programs.pipeline_step_text(1))
    c_blk = collective_counts(programs.pipeline_step_text(tp))
    report = {"collectives_tp1": c1, "collectives_tp2_blocking": c_blk}
    for mode in ("rsag", "matmul"):
        rules = [
            R.no_refused_pair(c1["all-reduce"], payload_only=False),
            R.min_collectives("reduce-scatter", 1, "decomposed rs half"),
            R.min_collectives("all-gather", 1, "decomposed ag half"),
        ]
        if mode == "matmul":
            rules.append(R.min_collectives(
                "collective-permute",
                c_blk["collective-permute"] + tp - 1,
                "chunked collective-matmul ring"))
        facts = _enforce(
            programs.pipeline_step_text(tp, comm_overlap=mode), rules,
            f"collective_matmul[{mode}]")
        report[f"collectives_tp2_{mode}"] = facts.counts
        if mode == "matmul":
            report["ring_collective_permutes"] = (
                facts.counts["collective-permute"]
                - c_blk["collective-permute"])
    report["model_axis_all_reduces_removed"] = (
        c_blk["all-reduce"] - c1["all-reduce"])
    return report


def probe_vocab_parallel() -> dict:
    """Vocab parallelism (``Pipeline(vocab_parallel=True)``), the memory
    claim, structurally: at tp=2 the vocab-sharded program's loss head
    never materializes a full-vocab buffer — no array shape in the whole
    optimized per-device module carries the vocab extent V (or its
    zero-padded V_pad; that also rules out a vocab-axis all-gather,
    whose result would be V-sized) — while the replicated tp=2 baseline
    carries the ``[V, H]`` table and ``[.., V]`` logits.  V is chosen so
    no other tensor dimension collides with it (93: odd, so the
    non-divisible zero-pad path compiles too; V_pad=94, shard=47)."""
    V = 93
    V_pad = V + (-V) % 2
    base_text = programs.pipeline_step_text(2, vocab_size=V)
    base = collective_counts(base_text)
    base_full = buffers_with_dim(base_text, V)
    assert base_full > 0, (
        "replicated baseline shows no full-vocab buffer — the probe's "
        "distinctive-dim scan is broken, not proving anything")
    vp_facts = _enforce(
        programs.pipeline_step_text(2, vocab_parallel=True, vocab_size=V),
        [R.no_buffer_with_dim((V, V_pad), "vocab"),
         R.min_collectives("collective-permute", 1, "pipeline ring")],
        "vocab_parallel[tp=2]")
    leaks = (vp_facts.buffers_with_dim(V)
             + vp_facts.buffers_with_dim(V_pad))
    return {"vocab_size": V, "padded_vocab": V_pad,
            "baseline_full_vocab_buffers": base_full,
            "vocab_parallel_full_vocab_buffers": leaks,
            "collectives_baseline": base,
            "collectives_vocab_parallel": vp_facts.counts}


def probe_zero3() -> dict:
    """ZeRO-2/3 on the tp×dp pipeline, structurally: the stage-3
    program stores parameters ONLY as flat shards across the step
    boundary (zero ENTRY-signature buffers of the distinctive extent,
    vs. the stage-0 baseline whose state carries them — a re-gather of
    full storage, or a re-materialization surviving into the returned
    state, fails here) while emitting >= one all-gather per (layer,
    leaf) — the per-layer on-demand gathers; a combiner pass collapsing
    them into one bulk up-front gather drops the count below
    layers x leaves and fails.  Stage 2 syncs gradients by
    reduce-scatter where the stage-0 baseline emits none."""
    DIM = programs.Z3_DIM
    t0 = programs.zero_step_text(0)
    c0 = collective_counts(t0)
    boundary0 = buffers_with_dim(entry_signature(t0), DIM)
    assert boundary0 > 0, (
        "stage-0 baseline shows no full-parameter buffer at the step "
        "boundary — the probe's distinctive-dim scan is broken, not "
        "proving anything")
    assert c0["reduce-scatter"] == 0, (
        f"stage-0 baseline unexpectedly reduce-scatters: {c0}")
    facts2 = _enforce(programs.zero_step_text(2), [
        R.min_collectives("reduce-scatter", 1, "ZeRO grad scatter"),
    ], "zero3[stage=2]")
    min_gathers = programs.Z3_V * programs.Z3_LEAVES
    facts3 = _enforce(programs.zero_step_text(3), [
        R.sharded_step_boundary(DIM),
        R.min_collectives("all-gather", min_gathers,
                          "per-layer ZeRO-3 gathers"),
        R.min_collectives("reduce-scatter", 1,
                          "gather custom-VJP grad scatter"),
    ], "zero3[stage=3]")
    return {"distinctive_dim": DIM,
            "boundary_full_param_buffers_stage0": boundary0,
            "boundary_full_param_buffers_stage3":
                facts3.boundary_buffers_with_dim(DIM),
            "min_per_layer_gathers": min_gathers,
            "collectives_stage0": c0,
            "collectives_stage2": facts2.counts,
            "collectives_stage3": facts3.counts}


def probe_decode() -> dict:
    """The serving engine's decode-step memory/dispatch claims,
    structurally: the vocab-parallel tp=2 program carries ZERO
    full-vocab buffers (vs the tp=1 baseline, which carries the ``[V,H]``
    table and ``[B,V]`` logits — the scan-validity control); neither
    program builds a ``[T, T]`` attention-score square (decode scores
    live at ``[B, heads, 1, T]``); the KV cache updates via in-place
    ``dynamic-update-slice`` (>= 2 per layer: k and v) with the cache
    buffers donated/aliased and no full-cache-sized copy anywhere; and
    the K-token window is ONE module with a fused ``while`` loop — one
    dispatch per K tokens, the ``run_steps`` property at decode time."""
    tp = 2
    base = programs.decode_step_text(1, False)
    vp = programs.decode_step_text(tp, True)
    V, T = programs.DEC_V, programs.DEC_T
    V_pad = V + (-V) % tp
    base_full = buffers_with_dim(base, V)
    assert base_full > 0, (
        "tp=1 baseline decode shows no full-vocab buffer — the probe's "
        "distinctive-dim scan is broken, not proving anything")
    report = {"vocab_size": V, "max_len": T,
              "baseline_full_vocab_buffers": base_full}
    for name, text, heads_local in (("tp1", base, 2), ("vp", vp, 1)):
        rules = R.rules_for_decode(
            tp if name == "vp" else 1, name == "vp",
            vocab_size=V, max_len=T,
            num_layers=programs.DEC_LAYERS,
            num_slots=programs.DEC_SLOTS, heads_local=heads_local,
            head_dim=programs.DEC_HEAD_DIM)
        facts = _enforce(text, rules, f"decode[{name}]")
        report[f"dynamic_update_slices_{name}"] = facts.dus
        report[f"collectives_{name}"] = facts.counts
    report["vocab_parallel_full_vocab_buffers"] = (
        buffers_with_dim(vp, V) + buffers_with_dim(vp, V_pad))
    return report


def probe_prefill() -> dict:
    """The serving engine's single-shot prefill computes the row it
    admits and nothing else, structurally: no buffer anywhere carries
    both the slot count and the prompt bucket (the ``[num_slots,
    prefill_len, ...]`` activations of a full-batch prefill — the
    one-row activations at ``[1, prefill_len, ...]`` are the
    scan-validity control); the row's keys and values land through
    in-place ``dynamic-update-slice`` (>= 2 per layer) on the donated,
    aliased cache, with no copy of a slot's lane — let alone of the
    whole ``[layers, slots, heads, max_len, head_dim]`` cache, in either
    layout; and the paged program holds no dense lane at all."""
    S, B, T = programs.PRE_LEN, programs.PRE_SLOTS, programs.DEC_T
    report = {"num_slots": B, "prefill_len": S, "max_len": T}
    for layout in ("dense", "paged"):
        text = programs.prefill_step_text(layout)
        rules = [R.no_host_transfer(), R.donated_alias(),
                 R.min_dus(2 * programs.DEC_LAYERS), R.no_collectives()]
        if layout == "paged":
            rules.append(R.paged_cache(B, T))
        else:
            # one slot's lane of one layer: heads x max_len x head_dim
            rules.append(R.no_donated_copy(
                T, 2 * T * programs.DEC_HEAD_DIM, "cache-lane"))
        facts = _enforce(text, rules, f"prefill[{layout}]")
        rows = facts.buffers_with_dim(S)
        assert rows > 0, (
            f"prefill[{layout}] shows no [.., {S}, ..] buffer — the "
            "probe's distinctive-dim scan is broken, not proving anything")
        batch = facts.buffers_with_dims((B, S))
        assert batch == 0, (
            f"prefill[{layout}]: {batch} buffer(s) shaped with both the "
            f"slot count {B} and the prompt bucket {S} — the program "
            "computes rows it does not admit")
        report[f"prompt_row_buffers_{layout}"] = rows
        report[f"slot_batch_buffers_{layout}"] = batch
        report[f"dynamic_update_slices_{layout}"] = facts.dus
    return report


def probe_quantized() -> dict:
    """The per-collective precision policy, structurally: quantization
    happens *inside* the program — convert-before, narrowed collective
    operand dtype, convert-after — exactly at the policied boundaries.

    * fp32 policy (the default) carries ZERO narrowed collectives — a
      lowering that silently narrows an un-policied boundary fails.
    * ``tp_psum=int8`` at blocking tp=2 carries >= 4 narrowed
      all-reduces (the Megatron out/wo forward psums and qkv/wi backward
      cotangent psums, on an fp16 levels wire) with the matching
      f16-in/f32-out convert pairs — while the dp grad sync, NOT
      policied in this program, keeps its payload-carrying fp32
      all-reduces (narrowing is per-boundary, not per-program).
    * ``tp_psum=int8`` + ``comm_overlap=rsag``: the decomposed pair
      stays un-re-fused (payload-carrying all-reduce count equals the
      tp=1 baseline's — the shared-scale pmaxes a quantized boundary
      adds are scalar and counted separately) and both halves narrow:
      the rs sums int8 levels on fp16, the ag rides a TRUE s8 wire.
    * full ``int8`` policy at zero_stage=3: the per-layer on-demand
      gathers carry narrowed payloads (>= one per (virtual stage,
      leaf)) and the backward cotangent reduce-scatter narrows too.
    """
    tp = 2
    _enforce(programs.pipeline_step_text(tp),
             [R.quantized_wire(clean=True)], "quantized[fp32]")
    n_fp32 = narrowed_collective_counts(programs.pipeline_step_text(tp))

    tp_only = (("tp_psum", "int8"),)
    q_facts = _enforce(
        programs.pipeline_step_text(tp, collective_precision=tp_only),
        [R.quantized_wire(mins={"all-reduce": 4})],
        "quantized[tp_psum=int8]")
    n_q, conv = q_facts.narrowed, q_facts.converts
    assert conv.get("f16", 0) >= n_q["all-reduce"], (
        f"missing convert-before halves: {conv} vs {n_q['all-reduce']} "
        "narrowed all-reduces")
    assert conv.get("f32", 0) >= 1, (
        f"missing convert-after halves (back to f32): {conv}")
    big_f32_ars = sum(1 for kind, dt, elems in q_facts.collectives
                      if kind == "all-reduce" and dt == "f32"
                      and elems > 1)
    assert big_f32_ars >= 1, (
        "tp_psum-only int8 policy narrowed the (un-policied) dp grad "
        "sync too — fp32 boundaries must stay untouched")

    c1_payload = nonscalar_all_reduces(programs.pipeline_step_text(1))
    rsag_facts = _enforce(
        programs.pipeline_step_text(tp, comm_overlap="rsag",
                                    collective_precision=tp_only),
        [R.no_refused_pair(c1_payload, payload_only=True),
         R.quantized_wire(mins={"reduce-scatter": 1, "all-gather": 1})],
        "quantized[rsag+int8]")
    s8_ags = sum(1 for kind, dt, _ in rsag_facts.collectives
                 if kind == "all-gather" and dt == "s8")
    assert s8_ags >= 1, (
        "the ag half of the quantized pair is not on a true s8 wire")

    min_gathers = programs.Z3_V * programs.Z3_LEAVES
    z3_facts = _enforce(
        programs.zero_step_text(3, "int8"),
        [R.quantized_wire(mins={"all-gather": min_gathers,
                                "reduce-scatter": 1})],
        "quantized[zero3+int8]")
    return {"narrowed_fp32_policy": n_fp32,
            "narrowed_tp_psum_int8": n_q,
            "converts_tp_psum_int8": {k: conv[k] for k in ("f16", "f32")
                                      if k in conv},
            "payload_f32_all_reduces_tp_psum_int8": big_f32_ars,
            "payload_all_reduces_tp1": c1_payload,
            "payload_all_reduces_rsag_int8":
                rsag_facts.payload_all_reduces(),
            "narrowed_rsag_int8": rsag_facts.narrowed,
            "s8_all_gathers_rsag_int8": s8_ags,
            "narrowed_zero3_int8": z3_facts.narrowed,
            "min_per_layer_gathers": min_gathers}


PROBES = {
    "steps_per_loop": probe_steps_per_loop,
    "single_replica": probe_single_replica,
    "pipeline_tp": probe_pipeline_tp,
    "collective_matmul": probe_collective_matmul,
    "vocab_parallel": probe_vocab_parallel,
    "zero3": probe_zero3,
    "quantized": probe_quantized,
    "decode": probe_decode,
    "prefill": probe_prefill,
}


def run_probes(names=None) -> tuple[dict, list]:
    """Run the named probes (default all); returns (report, failed)."""
    report, failed = {}, []
    for name in (names or list(PROBES)):
        try:
            report[name] = {"ok": True, **PROBES[name]()}
        except AssertionError as e:
            report[name] = {"ok": False, "error": str(e)}
            failed.append(name)
    return report, failed
