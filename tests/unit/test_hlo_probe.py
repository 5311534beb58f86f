"""HLO-structural falsifiability (tools/hlo_probe.py): the perf claims
the VERDICT demanded silicon-free proof for, asserted as collective
counts/kinds in compiled HLO on the simulated CPU mesh.

Tier-1 by design: a reintroduced single-replica all-reduce, a silently
re-fused monolithic TP all-reduce (the collective-matmul decomposition
undone by an XLA combiner pass or a code regression), or an unrolled
steps-per-loop scan each fail CI here, on CPU, before any hardware
window."""
import json

from tools.hlo_probe import (buffers_with_dim, buffers_with_dim_repeated,
                             collective_counts, collective_wire,
                             convert_counts, dynamic_update_slices,
                             entry_signature, large_copies_with_dim, main,
                             narrowed_collective_counts,
                             nonscalar_all_reduces,
                             probe_collective_matmul, probe_decode,
                             probe_pipeline_tp, probe_prefill,
                             probe_quantized,
                             probe_single_replica, probe_steps_per_loop,
                             probe_vocab_parallel, probe_zero3)


def test_collective_counts_parses_hlo_idioms():
    text = """
  %all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={{0,1}}
  %ag = (f32[4]{0}, f32[4]{0}) all-gather-start(f32[2]{0} %x), dimensions={0}
  %cp = f32[8]{0} collective-permute(f32[8]{0} %y), source_target_pairs={{0,1}}
  %fusion.all-reduce-ish = f32[] fusion(f32[] %z), kind=kLoop
"""
    counts = collective_counts(text)
    assert counts["all-reduce"] == 1
    assert counts["all-gather"] == 1
    assert counts["collective-permute"] == 1
    assert counts["reduce-scatter"] == 0 and counts["all-to-all"] == 0


def test_steps_per_loop_is_one_fused_dispatch():
    """k fused steps: one module, a while loop, and the one-step
    program's collective counts (scan body not unrolled)."""
    report = probe_steps_per_loop(k=4)
    assert report["fused_loop"]
    assert report["collectives_k_steps"] == report["collectives_one_step"]
    assert report["collectives_one_step"]["all-reduce"] >= 1


def test_single_replica_bypass_emits_zero_all_reduce():
    report = probe_single_replica()
    assert report["collectives"]["all-reduce"] == 0
    assert sum(report["collectives"].values()) == 0


def test_pipeline_tp_emits_model_axis_collectives():
    """tensor_parallel=2 adds the per-stage Megatron activation
    all-reduces (>= 4: out-proj + wo, forward + backward) on top of the
    tp=1 pipeline program, which itself carries the ppermute ring."""
    report = probe_pipeline_tp()
    assert report["collectives_tp1"]["collective-permute"] > 0
    assert report["collectives_tp2"]["collective-permute"] > 0
    assert report["model_axis_all_reduces"] >= 4


def test_collective_matmul_removes_monolithic_all_reduce():
    """The latency-hiding decomposition, structurally: the converted
    tp=2 program's all-reduce count EQUALS the tp=1 baseline's (zero
    monolithic model-axis all-reduce survives — and zero re-fuses: the
    count is exact, not an upper bound), the 'matmul' mode adds the
    >= tp-1 chunk-ring collective-permutes, and both modes emit the
    reduce-scatter/all-gather pairs the monolithic op decomposed into."""
    report = probe_collective_matmul()
    c1 = report["collectives_tp1"]
    for mode in ("rsag", "matmul"):
        c = report[f"collectives_tp2_{mode}"]
        assert c["all-reduce"] == c1["all-reduce"], (mode, c, c1)
        assert c["reduce-scatter"] >= 1 and c["all-gather"] >= 1, (mode, c)
    assert report["ring_collective_permutes"] >= 1
    assert report["model_axis_all_reduces_removed"] >= 4


def test_buffers_with_dim_parses_hlo_shapes():
    text = """
  %p0 = f32[8,8,93]{2,1,0} parameter(0)
  %t = (f32[93,16]{1,0}, s32[8,8]{1,0}) tuple(%a, %b)
  %c = bf16[47,16]{1,0} convert(f32[47,16]{1,0} %x)
"""
    assert buffers_with_dim(text, 93) == 2
    assert buffers_with_dim(text, 47) == 2
    assert buffers_with_dim(text, 94) == 0


def test_vocab_parallel_materializes_no_full_vocab_buffer():
    """The vocab-parallel memory claim, structurally: the sharded tp=2
    program's optimized HLO carries ZERO buffers of the (distinctive)
    vocab extent — no [B,L,V] logits, no replicated [V,H] table, no
    vocab-axis all-gather result — while the replicated baseline
    carries them; a silent re-replication of the loss head fails here,
    on CPU, before any hardware window."""
    report = probe_vocab_parallel()
    assert report["baseline_full_vocab_buffers"] > 0
    assert report["vocab_parallel_full_vocab_buffers"] == 0
    # the epilogue's model-axis collectives exist (lookup psum, stat
    # psums/pmax/pmin, backward hidden-cotangent psum)
    extra = (report["collectives_vocab_parallel"]["all-reduce"]
             - report["collectives_baseline"]["all-reduce"])
    assert extra >= 3, report


def test_entry_signature_extracts_step_boundary():
    text = """
HloModule m
%fused (p.0: f32[8,29]) -> f32[8,29] {
  %p.0 = f32[8,29]{1,0} parameter(0)
}
ENTRY %main.1 (Arg_0.1: f32[2,116], Arg_1.2: s32[8]) -> (f32[2,116]) {
  %big = f32[4,8,29]{2,1,0} all-gather(f32[2,116]{1,0} %x)
}
"""
    sig = entry_signature(text)
    # internal computations and step-internal temporaries are excluded
    assert buffers_with_dim(sig, 29) == 0
    assert buffers_with_dim(sig, 116) == 2


def test_decode_probe_helpers_parse_hlo_idioms():
    text = """
  %s = f32[3,2,57,57]{3,2,1,0} parameter(0)
  %dus = f32[2,3,1,57,8]{4,3,2,1,0} dynamic-update-slice(%a, %b, %i0)
  %dus2 = f32[8]{0} dynamic-update-slice-start(%c, %d, %i1)
  %cp = f32[3,1,8,57]{3,2,1,0} copy(f32[3,1,8,57]{2,3,1,0} %t)
  %cp2 = f32[4]{0} copy(f32[4]{0} %u)
"""
    assert buffers_with_dim_repeated(text, 57) == 1   # the [.., 57, 57]
    # times=1 degenerates to a per-shape scan (result + operand shapes)
    assert buffers_with_dim_repeated(text, 57, times=1) == 4
    assert dynamic_update_slices(text) == 2
    assert large_copies_with_dim(text, 57, 3 * 8 * 57) == 1
    assert large_copies_with_dim(text, 57, 10 ** 6) == 0


def test_decode_step_is_buffer_clean_and_in_place():
    """The serving decode claims, tier-1 on CPU: a vocab-parallel decode
    step that re-materializes full-vocab logits, builds a [T, T]
    attention square, regresses the KV write to copy-on-write, or
    unrolls the K-token window into separate dispatches fails CI here
    before any hardware window."""
    report = probe_decode()
    assert report["baseline_full_vocab_buffers"] > 0
    assert report["vocab_parallel_full_vocab_buffers"] == 0
    assert report["dynamic_update_slices_vp"] >= 4    # k+v x 2 layers
    assert report["collectives_vp"]["all-reduce"] >= 4
    assert sum(report["collectives_tp1"].values()) == 0


def test_prefill_computes_one_row_and_writes_it_in_place():
    """The serving prefill claims, tier-1 on CPU: a prefill that computes
    the ``[num_slots, prefill_len]`` slot batch again, or whose cache
    write regresses to a copy of a lane or of the whole cache, fails CI
    here — the failure PR 24 and PR 26 each met on the chip."""
    report = probe_prefill()
    for layout in ("dense", "paged"):
        assert report[f"prompt_row_buffers_{layout}"] > 0
        assert report[f"slot_batch_buffers_{layout}"] == 0
        assert report[f"dynamic_update_slices_{layout}"] >= 4  # k+v x 2


def test_prefill_probe_catches_the_slot_batch_and_the_cache_copy():
    """The scans the prefill probe rests on, on text shaped as the
    full-batch program's was: its ``[slots, bucket, ...]`` activations
    and a whole-cache layout copy are both seen."""
    from autodist_tpu.analysis.facts import ProgramFacts

    text = """
  %x = f32[5,11,16]{2,1,0} fusion(f32[5,11]{1,0} %tokens), kind=kLoop
  %row = f32[1,11,16]{2,1,0} fusion(f32[1,11]{1,0} %tokens), kind=kLoop
  %cp = f32[2,5,2,8,57]{4,3,2,1,0} copy(f32[2,5,2,8,57]{3,4,2,1,0} %kc)
"""
    facts = ProgramFacts.from_hlo(text)
    assert facts.buffers_with_dims((5, 11)) == 2
    assert facts.buffers_with_dim(11) == 4
    assert facts.large_copies_with_dim(57, 2 * 57 * 8) == 1


def test_narrowed_collective_helpers_parse_hlo_idioms():
    text = """
  %ar = f16[8]{0} all-reduce(f16[8]{0} %p), replica_groups={{0,1}}
  %mx = f32[] all-reduce(f32[] %s), to_apply=%max
  %big = f32[64]{0} all-reduce(f32[64]{0} %g)
  %ag = (s8[4]{0}, s8[8]{0}) all-gather-start(s8[4]{0} %x), dimensions={0}
  %rs = bf16[16]{0} reduce-scatter(bf16[32]{0} %y), dimensions={0}
  %c1 = f16[8]{0} convert(f32[8]{0} %a)
  %c2 = f32[8]{0} convert(f16[8]{0} %b)
"""
    n = narrowed_collective_counts(text)
    assert n["all-reduce"] == 1
    assert n["all-gather"] == 1
    assert n["reduce-scatter"] == 1
    # the scalar pmax is an all-reduce but not a payload one
    assert nonscalar_all_reduces(text) == 2
    wire = collective_wire(text)
    assert ("all-reduce", "f16", 8) in wire
    assert ("all-gather", "s8", 8) in wire
    conv = convert_counts(text)
    assert conv["f16"] == 1 and conv["f32"] == 1


def test_quantized_policy_narrows_the_wire():
    """The PR 8 acceptance probe, tier-1 on CPU: the int8-policy tp=2
    program carries the narrowed element type on every policied
    collective operand (convert pairs included), the fp32-policy
    program carries ZERO narrowed collectives, the quantized rs+ag
    pair stays un-re-fused, and the int8 ZeRO-3 gathers narrow per
    (virtual stage, leaf)."""
    report = probe_quantized()
    assert sum(report["narrowed_fp32_policy"].values()) == 0
    assert report["narrowed_tp_psum_int8"]["all-reduce"] >= 4
    assert report["converts_tp_psum_int8"]["f16"] >= 4
    assert report["payload_f32_all_reduces_tp_psum_int8"] >= 1
    assert (report["payload_all_reduces_rsag_int8"]
            == report["payload_all_reduces_tp1"])
    assert report["s8_all_gathers_rsag_int8"] >= 1
    assert (report["narrowed_zero3_int8"]["all-gather"]
            >= report["min_per_layer_gathers"])
    assert report["narrowed_zero3_int8"]["reduce-scatter"] >= 1


def test_zero3_shards_step_boundary_and_gathers_per_layer():
    """The ZeRO-2/3 re-materialization guard, tier-1 on CPU: a stage-3
    program whose returned state regains a full parameter (e.g. a
    reintroduced update all-gather), whose per-layer gathers collapse
    into one bulk materialization (a collective-combiner pass undoing
    the chain), or whose stage-2 grad sync regresses to an all-reduce,
    fails CI here before any hardware window."""
    report = probe_zero3()
    assert report["boundary_full_param_buffers_stage0"] > 0
    assert report["boundary_full_param_buffers_stage3"] == 0
    assert (report["collectives_stage3"]["all-gather"]
            >= report["min_per_layer_gathers"])
    assert report["collectives_stage2"]["reduce-scatter"] >= 1
    assert report["collectives_stage0"]["reduce-scatter"] == 0


def test_probe_cli_json_output(tmp_path, capsys):
    """--json writes the machine-readable report; --probe selects a
    subset so the CLI contract is testable without recompiling every
    program."""
    out = tmp_path / "probe.json"
    rc = main(["--probe", "single_replica", "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert set(report) == {"single_replica"}
    assert report["single_replica"]["ok"] is True
    assert report["single_replica"]["collectives"]["all-reduce"] == 0
    printed = json.loads(capsys.readouterr().out)
    assert printed == report
