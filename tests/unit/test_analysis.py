"""The static-analysis subsystem (autodist_tpu/analysis/): diagnostics
vocabulary, parsed-HLO facts extraction, plan-lint rules over the
Strategy IR, program-lint rules over compiled programs, and — the
falsifiability backbone — the mutation matrix proving every shipped
rule fires on its seeded violation and stays silent on the honest
artifact.

Program-mutation tests compile from the same memoized corpus the HLO
probes use (autodist_tpu/analysis/programs.py), so within one pytest
process each 8-device program compiles once for probes, rules, and
mutations alike.
"""
import json
import os

import pytest

from autodist_tpu.analysis import (CODES, Diagnostic, LintReport,
                                   ProgramFacts, lint_plan, lint_program,
                                   rules_for_decode, rules_for_strategy)
from autodist_tpu.analysis import program_rules as R
from autodist_tpu.analysis.diagnostics import ERROR, WARNING
from autodist_tpu.analysis.mutations import (_pipeline_fixture,
                                             all_mutations,
                                             run_mutations)

DATA = os.path.join(os.path.dirname(__file__), "data")


# --------------------------------------------------------------------------- #
# Diagnostics vocabulary
# --------------------------------------------------------------------------- #
def test_diagnostic_codes_are_registered_and_defaulted():
    d = Diagnostic("ADT105", "boom", where="prog")
    assert d.severity == ERROR           # the code's registered default
    assert "ADT105" in str(d) and "[prog]" in str(d)
    with pytest.raises(KeyError):
        Diagnostic("ADT999", "unregistered")


def test_lint_report_severity_accessors_and_json():
    rep = LintReport([Diagnostic("ADT105", "e"),
                      Diagnostic("ADT030", "w")])
    assert len(rep.errors) == 1 and len(rep.warnings) == 1
    assert not rep.ok
    payload = json.loads(rep.to_json())
    assert payload["errors"] == 1 and payload["ok"] is False
    assert payload["diagnostics"][0]["code"] == "ADT105"  # errors first


def test_every_code_has_severity_and_summary():
    for code, (severity, summary) in CODES.items():
        assert severity in (ERROR, WARNING, "info"), code
        assert summary, code


# --------------------------------------------------------------------------- #
# Facts extraction on synthetic HLO
# --------------------------------------------------------------------------- #
_SYNTHETIC = """
HloModule m, input_output_alias={ {0}: (0, {}, may-alias) }
%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
}
ENTRY %main (Arg_0: f32[2,116], Arg_1: s32[8]) -> (f32[2,116]) {
  %w = f32[2,116]{1,0} while(f32[2,116]{1,0} %init), body=%body
  %ar = f16[64]{0} all-reduce(f16[64]{0} %x), replica_groups={{0,1}}
  %sc = f32[] all-reduce(f32[] %s), to_apply=%max
  %ag = (s8[4]{0}, s8[8]{0}) all-gather-start(s8[4]{0} %y), dimensions={0}
  %ob = f32[8]{0} opt-barrier(f32[8]{0} %z)
  %snd = f32[8]{0} send(f32[8]{0} %z, token[] %tk), channel_id=3
  %dus = f32[3,57,8]{2,1,0} dynamic-update-slice(%a, %b, %i)
  %cp = f32[3,57,8]{2,1,0} copy(f32[3,57,8]{1,2,0} %t)
  %c1 = f16[64]{0} convert(f32[64]{0} %q)
}
"""


def test_program_facts_from_synthetic_hlo():
    f = ProgramFacts.from_hlo(_SYNTHETIC)
    assert f.counts["all-reduce"] == 2
    assert f.counts["all-gather"] == 1
    assert f.narrowed["all-reduce"] == 1       # the f16 payload one
    assert f.narrowed["all-gather"] == 1       # the s8 wire
    assert f.payload_all_reduces() == 1        # scalar pmax excluded
    assert f.converts == {"f16": 1}
    assert f.dus == 1
    assert f.host_transfers == 1               # the send
    assert f.barriers == 1
    assert f.fused_loop and f.io_alias
    assert f.entry.startswith("ENTRY ")
    assert f.boundary_buffers_with_dim(116) == 2
    assert f.boundary_buffers_with_dim(57) == 0  # step-internal only
    assert f.buffers_with_dim(57) == 3   # dus result + copy both sides
    assert f.large_copies_with_dim(57, 3 * 57 * 8) == 1
    assert f.gathers_larger_than(4) == 1


def test_block_table_gather_found_in_either_operand_spelling():
    """ADT115's evidence is structural: the gather's first operand
    carries the pool extent, whether the HLO printer spells the
    operand's shape inline or (jax 0.9.0) by name only."""
    from autodist_tpu.analysis.facts import gathers_with_operand_dim

    inline = ("  %g = f32[3,4]{1,0} gather(f32[13,2,16,8]{3,2,1,0} %pool, "
              "s32[3,4]{1,0} %tab), offset_dims={1}\n"
              "  %ag = f32[26]{0} all-gather(f32[13]{0} %p)\n")
    by_name = ("  %pool = f32[13,2,16,8]{3,2,1,0} parameter(0)\n"
               "  ROOT %g = f32[3,4]{1,0} gather(%pool, %tab), "
               "offset_dims={1}\n"
               "  %g2 = f32[3]{0} gather(%undefined, %tab)\n")
    assert gathers_with_operand_dim(inline, 13) == 1
    assert gathers_with_operand_dim(by_name, 13) == 1
    assert gathers_with_operand_dim(by_name, 16) == 1
    assert gathers_with_operand_dim(by_name, 99) == 0


def test_host_transfer_variants_detected():
    from autodist_tpu.analysis.facts import host_transfers
    assert host_transfers("  %r = (f32[2]) recv(token[] %t)") == 1
    assert host_transfers("  %o = token[] outfeed(f32[2] %x)") == 1
    assert host_transfers(
        '  %h = f32[2] custom-call(%x), custom_call_target='
        '"MoveToHost"') == 1
    assert host_transfers("  %m = f32[2] multiply(%a, %b)") == 0


# --------------------------------------------------------------------------- #
# Program rules on synthetic text (each rule both ways, no compiles)
# --------------------------------------------------------------------------- #
def _clean_text():
    return """
ENTRY %main (Arg_0: f32[4,8]) -> f32[4,8] {
  %w = f32[4,8]{1,0} while(f32[4,8]{1,0} %x), body=%b
}
""" + "input_output_alias={}"


@pytest.mark.parametrize("rule,bad_line", [
    (R.no_host_transfer(),
     "  %s = f32[8]{0} send(f32[8]{0} %x, token[] %t), channel_id=1"),
    (R.no_buffer_with_dim((93,), "vocab"),
     "  %t = f32[8,93]{1,0} parameter(7)"),
    (R.no_score_square(57),
     "  %sq = f32[2,57,57]{2,1,0} multiply(%a, %b)"),
    (R.no_full_gather(100),
     "  %g = f32[4096]{0} all-gather(f32[1024]{0} %p), dimensions={0}"),
    (R.no_collectives(),
     "  %ar = f32[8]{0} all-reduce(f32[8]{0} %g), replica_groups={}"),
    (R.quantized_wire(clean=True),
     "  %ar = f16[8]{0} all-reduce(f16[8]{0} %g), replica_groups={}"),
])
def test_injection_rules_fire_exactly_on_the_violation(rule, bad_line):
    clean = _clean_text()
    assert lint_program(clean, [rule]).ok
    report = lint_program(clean + "\n" + bad_line, [rule])
    assert report.codes() == {rule.code}


def test_threshold_rules_both_ways():
    two_dus = ("%d1 = f32[8] dynamic-update-slice(%a,%b,%i)\n"
               "%d2 = f32[8] dynamic-update-slice(%c,%e,%j)\n")
    assert lint_program(two_dus, [R.min_dus(2)]).ok
    assert not lint_program(two_dus, [R.min_dus(3)]).ok
    gathers = "%g = f32[8]{0} all-gather(f32[4]{0} %p), dimensions={0}\n"
    assert lint_program(gathers * 3, [R.min_collectives(
        "all-gather", 3, "per-layer")]).ok
    assert not lint_program(gathers * 2, [R.min_collectives(
        "all-gather", 3, "per-layer")]).ok
    ar = "%r = f32[64]{0} all-reduce(f32[64]{0} %g), to_apply=%add\n"
    assert lint_program(ar * 2, [R.no_refused_pair(2)]).ok
    assert not lint_program(ar * 3, [R.no_refused_pair(2)]).ok
    assert not lint_program(ar, [R.no_refused_pair(2)]).ok


# --------------------------------------------------------------------------- #
# Plan lint
# --------------------------------------------------------------------------- #
def test_builder_strategies_plan_clean():
    """Every builder-produced fixture passes plan lint with zero
    ERRORs (warnings are allowed: degrades are promoted, not fatal)."""
    for kwargs in ({}, {"tensor_parallel": 2},
                   {"tensor_parallel": 2, "vocab_parallel": True},
                   {"tensor_parallel": 2, "zero_stage": 3,
                    "collective_precision": "int8"}):
        strategy, spec, trainable = _pipeline_fixture(**kwargs)
        report = lint_plan(strategy, resource_spec=spec,
                           trainable=trainable)
        assert report.ok, (kwargs, report.render())


def test_plan_lint_works_without_resource_spec():
    """A serialized plan lints standalone: the declared mesh_axes stand
    in for the topology (the hand-edited-JSON audit path)."""
    strategy, _, _ = _pipeline_fixture(tensor_parallel=2)
    report = lint_plan(strategy)
    assert report.ok
    d = json.loads(strategy.to_json())
    d["graph_config"]["parallel"]["tensor_parallel"] = 4
    from autodist_tpu.strategy.ir import Strategy
    mutated = lint_plan(Strategy.from_json(json.dumps(d)))
    assert "ADT005" in mutated.codes()


def test_plan_lint_golden_report():
    """Diagnostic golden: a deterministic everything-wrong-at-once plan
    renders byte-identically (message wording and ordering are part of
    the operator contract; regenerate deliberately when a rule
    sharpens its message)."""
    from autodist_tpu.strategy.ir import Strategy

    strategy, spec, trainable = _pipeline_fixture(tensor_parallel=2)
    d = json.loads(strategy.to_json())
    d["id"] = "golden"
    d["graph_config"]["replicas"] = 4
    d["graph_config"]["parallel"]["comm_overlap"] = "ring"
    d["graph_config"]["precision"] = {"vocab_stats": "int8"}
    for nc in d["node_configs"]:
        if nc["var_name"] == "stages/mlp/wi/kernel":
            nc["synchronizer"] = {
                "kind": "ps", "zero_stage": 3,
                "reduction_destination": "",
                "local_replication": False, "sync": True,
                "staleness": 0}
    report = lint_plan(Strategy.from_json(json.dumps(d)),
                       resource_spec=spec, trainable=trainable)
    golden = open(os.path.join(DATA, "plan_lint_golden.txt")).read()
    assert report.render(title="golden-plan") + "\n" == golden


def test_degraded_diagnostics_is_the_shared_code_path():
    """lowered.zero_degraded records surface as ADT034 — the one code
    path both lint_plan(lowered=...) and callers holding a lowered
    plan use."""
    from types import SimpleNamespace

    from autodist_tpu.analysis import degraded_diagnostics

    strategy, spec, trainable = _pipeline_fixture(tensor_parallel=2)
    lowered = SimpleNamespace(zero_degraded={"stages/x": "because"})
    report = lint_plan(strategy, resource_spec=spec,
                       trainable=trainable, lowered=lowered)
    assert [d.where for d in report.by_code("ADT034")] == ["stages/x"]
    direct = list(degraded_diagnostics({"stages/x": "because"}))
    assert direct[0].to_dict() == report.by_code("ADT034")[0].to_dict()


# --------------------------------------------------------------------------- #
# Deriving program contracts from the Strategy IR
# --------------------------------------------------------------------------- #
def _rule_codes(rules):
    return {r.code for r in rules}


def test_rules_for_strategy_derivation():
    plain, _, _ = _pipeline_fixture()
    codes = _rule_codes(rules_for_strategy(plain))
    assert {"ADT101", "ADT109"} <= codes       # host + fp32-clean wire

    vocab, _, _ = _pipeline_fixture(tensor_parallel=2,
                                    vocab_parallel=True)
    assert "ADT105" in _rule_codes(
        rules_for_strategy(vocab, vocab_size=93))

    z3, _, _ = _pipeline_fixture(tensor_parallel=2, zero_stage=3,
                                 collective_precision="int8")
    codes = _rule_codes(rules_for_strategy(z3, boundary_dim=29))
    assert {"ADT106", "ADT107", "ADT109"} <= codes

    overlap, _, _ = _pipeline_fixture(tensor_parallel=2,
                                      comm_overlap="rsag")
    assert "ADT107" in _rule_codes(rules_for_strategy(overlap))


def test_rules_for_decode_derivation():
    codes = _rule_codes(rules_for_decode(
        2, True, vocab_size=93, max_len=57, num_layers=2, num_slots=3,
        heads_local=1, head_dim=8))
    assert {"ADT102", "ADT103", "ADT104", "ADT105", "ADT111",
            "ADT112", "ADT114"} <= codes
    tp1 = _rule_codes(rules_for_decode(
        1, False, vocab_size=93, max_len=57, num_layers=2, num_slots=3,
        heads_local=2, head_dim=8))
    assert "ADT113" in tp1 and "ADT105" not in tp1


# --------------------------------------------------------------------------- #
# The mutation matrix (the acceptance harness)
# --------------------------------------------------------------------------- #
def test_mutation_matrix_covers_the_required_rules():
    codes = {m.code for m in all_mutations()}
    # the acceptance list: re-fusion barrier, full-vocab buffer,
    # full-param step boundary, quantized wire, host transfer,
    # donated copy — plus the rest of the shipped rules
    assert {"ADT108", "ADT105", "ADT106", "ADT109", "ADT101",
            "ADT103", "ADT104", "ADT115"} <= codes
    assert len(codes) >= 10


def test_plan_mutations_fire():
    """Every plan rule fires on its seeded hand-edit and stays silent
    on the builder's own output (cheap: no compiles)."""
    results = run_mutations(kinds=["plan"])
    assert results
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad


def test_program_mutations_fire():
    """Every program rule fires on its seeded violation (doctored HLO
    or the broken-sibling program) and passes the honest compiled
    program — compiles ride the shared memoized corpus."""
    results = run_mutations(kinds=["program"])
    assert results
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad


def test_supervision_mutations_fire():
    """The ADT08x matrix: every supervision rule fires on its doctored
    config and stays silent on the honest one (escalation without a
    saver, heartbeat interval >= timeout, restart backoff beyond the
    SSP staleness window)."""
    results = run_mutations(kinds=["supervision"])
    assert {r["code"] for r in results} == {"ADT080", "ADT081", "ADT082"}
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad


def test_lint_supervision_clean_config_is_clean():
    from autodist_tpu.analysis import lint_supervision
    from autodist_tpu.analysis.mutations import _supervision_fixture

    config, strategy = _supervision_fixture()
    assert lint_supervision(config, strategy=strategy).ok
    # dict form (a serialized config) lints identically
    assert lint_supervision(config.to_dict(), strategy=strategy).ok
    # ADT082 needs SSP in the plan: without a strategy the backoff rule
    # cannot fire, the others still do
    import dataclasses as dc

    broken = dc.replace(config, saver=None)
    report = lint_supervision(broken)
    assert "ADT080" in report.codes() and not report.ok
