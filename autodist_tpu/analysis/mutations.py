"""Mutation-test harness: prove every shipped lint rule actually fires.

A rule that never fires is indistinguishable from a rule that is
broken, so each shipped rule pairs with at least one *seeded
violation*:

* **plan mutations** — take a real builder-produced Strategy, apply a
  JSON-level hand-edit (re-replicate a shard's ZeRO, orphan a precision
  slot, disagree the comm_overlap records, break the mesh…), and assert
  the plan linter reports the expected ``ADT0xx`` code — and did NOT
  report it on the unmutated plan.
* **program mutations** — take a real compiled program from the corpus
  and either doctor its HLO text (inject a host transfer, strip the
  fused loop, drop the donation aliasing…) or swap in the program a
  broken lowering WOULD have produced (the blocking program for
  "barrier removed", the fp32 program for "precision policy dropped",
  the replicated program for "shard re-replicated") — and assert the
  program rule fires, having passed on the honest text.

``tools/lint_strategy.py --mutate`` runs the whole matrix and fails if
any rule does not discriminate.
"""
from __future__ import annotations

import dataclasses
import json
from types import SimpleNamespace
from typing import Callable, Optional

from autodist_tpu.analysis import program_rules as R
from autodist_tpu.analysis import programs
from autodist_tpu.analysis.facts import (collective_counts,
                                         nonscalar_all_reduces)
from autodist_tpu.analysis.plan_rules import lint_plan
from autodist_tpu.analysis.program_rules import lint_program


# --------------------------------------------------------------------------- #
# Cheap plan fixtures (strategy building only — no compiles)
# --------------------------------------------------------------------------- #
def _lm_trainable(vocab_size: int = 32):
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
    from autodist_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=vocab_size, hidden_size=16,
                            num_layers=2, num_heads=2, mlp_dim=32,
                            max_len=8, dtype=jnp.float32,
                            dropout_rate=0.0, attention_dropout_rate=0.0)
    return make_pipeline_lm_trainable(cfg, optax.sgd(0.05),
                                      jax.random.PRNGKey(0))


def _tp_mesh_spec():
    from autodist_tpu.resource import ResourceSpec

    return ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 8},
                         "mesh": {"data": 2, "pipe": 2, "model": 2}})


def _dp_mesh_spec():
    from autodist_tpu.resource import ResourceSpec

    return ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 8},
                         "mesh": {"data": 4, "pipe": 2}})


def _pipeline_fixture(**builder_kwargs):
    """(strategy, resource_spec, trainable) for a Pipeline variant on
    the tiny LM; tp>1 variants get the 3-axis mesh."""
    from autodist_tpu.strategy.parallel_builders import Pipeline

    tp = builder_kwargs.get("tensor_parallel", 1)
    spec = _tp_mesh_spec() if tp > 1 else _dp_mesh_spec()
    trainable = _lm_trainable()
    strategy = Pipeline(num_microbatches=2, **builder_kwargs).build(
        trainable, spec)
    return strategy, spec, trainable


def _pipe_only_fixture():
    """Pipeline on a pipe-only mesh (no data axis) — the fixture the
    compressor-without-data-axis rule needs a clean base on."""
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.strategy.parallel_builders import Pipeline

    spec = ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 2},
                         "mesh": {"pipe": 2}})
    trainable = _lm_trainable()
    strategy = Pipeline(num_microbatches=2).build(trainable, spec)
    return strategy, spec, trainable


def _multislice_fixture():
    """Pipeline on a two-slice (dcn x data x pipe) mesh — the fixture
    the dcn-axis-misuse rule needs a clean multi-slice base on."""
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.strategy.parallel_builders import Pipeline

    spec = ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 8},
                         "mesh": {"dcn": 2, "data": 2, "pipe": 2}})
    trainable = _lm_trainable()
    strategy = Pipeline(num_microbatches=2).build(trainable, spec)
    return strategy, spec, trainable


def _expert_fixture(mesh=None, **builder_kwargs):
    """dp×expert MoE plan through the ExpertParallel builder — the base
    the moe_a2a precision / a2a_ring kernel / expert placement rules
    mutate against."""
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu.models.moe_transformer import (MoeConfig,
                                                     make_moe_lm_trainable)
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.strategy.parallel_builders import ExpertParallel

    mesh = dict(mesh or {"data": 2, "expert": 2})
    n = 1
    for v in mesh.values():
        n *= v
    spec = ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": n},
                         "mesh": mesh})
    cfg = MoeConfig(vocab_size=32, hidden_size=16, num_layers=1,
                    num_heads=2, expert_hidden=32, num_experts=4,
                    max_len=8, dtype=jnp.float32)
    trainable = make_moe_lm_trainable(cfg, optax.sgd(0.05),
                                      jax.random.PRNGKey(0),
                                      batch_size=4, seq_len=8)
    strategy = ExpertParallel(num_experts=4,
                              **builder_kwargs).build(trainable, spec)
    return strategy, spec, trainable


def _fsdp_fixture():
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.strategy.gspmd_builders import FSDPSharded

    spec = ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 8}})
    trainable = programs.tiny_trainable()
    return FSDPSharded(min_size=1).build(trainable, spec), spec, trainable


# --------------------------------------------------------------------------- #
# Mutation records
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class PlanMutation:
    """Hand-edit a strategy's JSON dict; ``code`` must appear after."""

    name: str
    code: str
    description: str
    fixture: Callable
    mutate: Callable[[dict], dict]
    lowered_factory: Optional[Callable] = None   # ADT034: degrade record
    kind: str = "plan"

    def run(self) -> dict:
        from autodist_tpu.strategy.ir import Strategy

        strategy, spec, trainable = self.fixture()
        clean = lint_plan(strategy, resource_spec=spec,
                          trainable=trainable)
        d = json.loads(strategy.to_json())
        mutated_strategy = Strategy.from_json(json.dumps(self.mutate(d)))
        lowered = self.lowered_factory() if self.lowered_factory else None
        mutated = lint_plan(mutated_strategy, resource_spec=spec,
                            trainable=trainable, lowered=lowered)
        return {"name": self.name, "kind": self.kind, "code": self.code,
                "clean_ok": self.code not in clean.codes(),
                "fired": self.code in mutated.codes(),
                "description": self.description}


@dataclasses.dataclass
class ProgramMutation:
    """Doctor a compiled program's text (or swap in a broken sibling
    program); ``code`` must fire on the result and not on the honest
    text."""

    name: str
    code: str
    description: str
    text: Callable[[], str]
    rules: Callable[[], list]
    mutate: Callable[[str], str]
    kind: str = "program"

    def run(self) -> dict:
        text = self.text()
        rules = self.rules()
        clean = lint_program(text, rules, where=self.name)
        mutated = lint_program(self.mutate(text), rules, where=self.name)
        return {"name": self.name, "kind": self.kind, "code": self.code,
                "clean_ok": self.code not in clean.codes(),
                "fired": self.code in mutated.codes(),
                "description": self.description}


@dataclasses.dataclass
class ReshardMutation:
    """Doctor an elastic state-codec manifest pair (a hand-edited
    checkpoint sidecar / a wrong target); the reshard compatibility
    lint must fire on the doctored pair and stay silent on the honest
    one."""

    name: str
    code: str
    description: str
    mutate: Callable  # (src_manifest, dst_manifest) -> (src, dst)
    kind: str = "reshard"

    def run(self) -> dict:
        import copy

        from autodist_tpu.analysis.plan_rules import lint_reshard

        src_r, dst_r = programs._reshard_pair()
        src = src_r.lowered.state_manifest(src_r.state)
        dst = dst_r.lowered.state_manifest(dst_r.state)
        clean = lint_reshard(src, dst)
        m_src, m_dst = self.mutate(copy.deepcopy(src), copy.deepcopy(dst))
        mutated = lint_reshard(m_src, m_dst)
        return {"name": self.name, "kind": self.kind, "code": self.code,
                "clean_ok": self.code not in clean.codes(),
                "fired": self.code in mutated.codes(),
                "description": self.description}


def _supervision_fixture():
    """A CLEAN supervised-recovery config (saver attached, sane
    heartbeat cadence, restart backoff inside the SSP window) over a
    staleness-2 SSP strategy — the base every ADT08x mutation doctors."""
    from autodist_tpu.runtime.cluster import SupervisionConfig
    from autodist_tpu.runtime.retry import RetryPolicy
    from autodist_tpu.strategy.ir import (GraphConfig, NodeConfig,
                                          PSSynchronizer, Strategy)

    strategy = Strategy(
        node_configs=[NodeConfig(var_name="w",
                                 synchronizer=PSSynchronizer(staleness=2))],
        graph_config=GraphConfig(replicas=1))
    config = SupervisionConfig(
        max_restarts=1,
        restart_backoff=RetryPolicy(max_attempts=2, base_delay_s=0.2,
                                    cap_delay_s=0.2, jitter=0.5),
        heartbeat_interval_s=0.5, heartbeat_timeout_s=3.0,
        escalate=True, saver=object(), step_time_estimate_s=1.0)
    return config, strategy


@dataclasses.dataclass
class SupervisionMutation:
    """Doctor a clean SupervisionConfig; the supervision lint must fire
    ``code`` on the doctored config and stay silent on the honest one."""

    name: str
    code: str
    description: str
    mutate: Callable  # (SupervisionConfig) -> SupervisionConfig
    kind: str = "supervision"

    def run(self) -> dict:
        from autodist_tpu.analysis.plan_rules import lint_supervision

        config, strategy = _supervision_fixture()
        clean = lint_supervision(config, strategy=strategy)
        mutated = lint_supervision(self.mutate(config), strategy=strategy)
        return {"name": self.name, "kind": self.kind, "code": self.code,
                "clean_ok": self.code not in clean.codes(),
                "fired": self.code in mutated.codes(),
                "description": self.description}


def _supervision_mutations() -> list[SupervisionMutation]:
    import dataclasses as dc

    from autodist_tpu.runtime.retry import RetryPolicy

    return [
        SupervisionMutation(
            "escalation_without_saver", "ADT080",
            "escalate=True with the saver detached — shrink-to-"
            "survivors would resume from nothing (silent state loss)",
            lambda c: dc.replace(c, saver=None)),
        SupervisionMutation(
            "heartbeat_interval_beyond_timeout", "ADT081",
            "heartbeat interval raised past the timeout — every "
            "healthy worker declared dead between beats",
            lambda c: dc.replace(c, heartbeat_interval_s=5.0)),
        SupervisionMutation(
            "restart_backoff_outlasts_ssp_window", "ADT082",
            "restart backoff cap raised beyond the SSP staleness "
            "window — peers stall at the gate on every restart",
            lambda c: dc.replace(c, restart_backoff=RetryPolicy(
                max_attempts=6, base_delay_s=2.0, cap_delay_s=30.0))),
    ]


def _fleet_fixture():
    """A CLEAN serving-fleet shape on a two-slice 8-device topology
    (2 replicas of a tp=2 group, hedge deadline well under the request
    deadline, sane heartbeat cadence, replacement budget backed by an
    engine source) — the base every ADT085+ mutation doctors."""
    from autodist_tpu.resource import ResourceSpec

    spec = ResourceSpec({"topology": {"num_devices": 8, "num_slices": 2}})
    config = {"replicas": 2, "tensor_parallel": 2, "kv_layout": "paged",
              "hedge_timeout_s": 0.5, "request_deadline_s": 10.0,
              "max_replacements": 1, "has_engine_source": True,
              "heartbeat_interval_s": 0.5, "heartbeat_timeout_s": 5.0}
    return config, spec


@dataclasses.dataclass
class FleetMutation:
    """Doctor a clean fleet config; the fleet lint must fire ``code``
    on the doctored shape and stay silent on the honest one."""

    name: str
    code: str
    description: str
    mutate: Callable  # (dict) -> dict
    kind: str = "fleet"

    def run(self) -> dict:
        from autodist_tpu.analysis.plan_rules import lint_fleet

        config, spec = _fleet_fixture()
        clean = lint_fleet(config, resource_spec=spec)
        mutated = lint_fleet(self.mutate(dict(config)),
                             resource_spec=spec)
        return {"name": self.name, "kind": self.kind, "code": self.code,
                "clean_ok": self.code not in clean.codes(),
                "fired": self.code in mutated.codes(),
                "description": self.description}


def _fleet_mutations() -> list[FleetMutation]:
    return [
        FleetMutation(
            "hedge_beyond_request_deadline", "ADT085",
            "hedge timeout raised past the request deadline — every "
            "request expires before its hedge can fire (the straggler "
            "path is dead config)",
            lambda c: dict(c, hedge_timeout_s=20.0)),
        FleetMutation(
            "fleet_overflows_topology", "ADT086",
            "replica count raised until replicas x tp exceeds the "
            "device budget",
            lambda c: dict(c, replicas=8)),
        FleetMutation(
            "replacement_without_engine_source", "ADT087",
            "replacement budget kept but the engine source detached — "
            "every replica death or drain escalates to a permanent "
            "shrink",
            lambda c: dict(c, has_engine_source=False)),
        FleetMutation(
            "fleet_tp_across_dcn", "ADT088",
            "tp degree raised past a slice's ICI degree — the "
            "per-token boundary all-reduces would ride DCN",
            lambda c: dict(c, replicas=1, tensor_parallel=8)),
    ]


def _disagg_fixture():
    """A CLEAN disaggregated pool split on a two-slice 8-device
    topology (1 prefill + 2 decode replicas of a tp=2 group: 6 of 8
    devices, tp well within a slice's 4-device ICI) — the base every
    ADT089 mutation doctors."""
    from autodist_tpu.resource import ResourceSpec

    spec = ResourceSpec({"topology": {"num_devices": 8, "num_slices": 2}})
    config = {"prefill_replicas": 1, "decode_replicas": 2,
              "tensor_parallel": 2, "kv_layout": "paged"}
    return config, spec


@dataclasses.dataclass
class DisaggMutation:
    """Doctor a clean disaggregated pool split; the disagg lint must
    fire ``code`` on the doctored shape and stay silent on the honest
    one."""

    name: str
    code: str
    description: str
    mutate: Callable  # (dict) -> dict
    kind: str = "disagg"

    def run(self) -> dict:
        from autodist_tpu.analysis.plan_rules import lint_disagg

        config, spec = _disagg_fixture()
        clean = lint_disagg(config, resource_spec=spec)
        mutated = lint_disagg(self.mutate(dict(config)),
                              resource_spec=spec)
        return {"name": self.name, "kind": self.kind, "code": self.code,
                "clean_ok": self.code not in clean.codes(),
                "fired": self.code in mutated.codes(),
                "description": self.description}


def _disagg_mutations() -> list[DisaggMutation]:
    return [
        DisaggMutation(
            "disagg_pools_overflow_topology", "ADT089",
            "decode pool grown until (prefill + decode) x tp exceeds "
            "the device budget — the elected split cannot be placed",
            lambda c: dict(c, decode_replicas=4)),
        DisaggMutation(
            "disagg_decode_tp_across_dcn", "ADT089",
            "decode-pool tp degree raised past a slice's ICI degree — "
            "decode's per-token boundary all-reduces would ride DCN",
            lambda c: dict(c, prefill_replicas=1, decode_replicas=1,
                           tensor_parallel=8)),
    ]


def _handoff_fixture() -> dict:
    """An HONEST prefill→decode handoff plan: 4 prefix blocks routed
    through the compiled per-block gathers, each participant staging
    4 blocks' worth of one pool shard — an order of magnitude under
    the shard budget (one full per-device pool shard)."""
    return {"prefill_replica": "prefill-0", "decode_replica": "decode-0",
            "blocks": 4, "per_device_gather_elems": 4 * 640,
            "budget_elems": 64 * 640}


@dataclasses.dataclass
class HandoffMutation:
    """Doctor an honest KV handoff plan; the handoff lint must fire
    ``code`` on the doctored plan and stay silent on the honest one."""

    name: str
    code: str
    description: str
    mutate: Callable  # (dict) -> dict
    kind: str = "handoff"

    def run(self) -> dict:
        from autodist_tpu.analysis.plan_rules import lint_handoff

        plan = _handoff_fixture()
        clean = lint_handoff(plan)
        mutated = lint_handoff(self.mutate(dict(plan)))
        return {"name": self.name, "kind": self.kind, "code": self.code,
                "clean_ok": self.code not in clean.codes(),
                "fired": self.code in mutated.codes(),
                "description": self.description}


def _handoff_mutations() -> list[HandoffMutation]:
    return [
        HandoffMutation(
            "handoff_gathers_full_pool", "ADT072",
            "the per-block route is replaced by a full-pool staging — "
            "every participant materializes the whole pool instead of "
            "the request's prefix blocks",
            lambda p: dict(p, blocks=64,
                           per_device_gather_elems=4 * 64 * 640)),
    ]


def _block_trace_fixture() -> list:
    """An HONEST allocator event trace: the exact sequence the serving
    engine's prefix-caching path produces for two requests sharing a
    3-block prompt (2 full blocks + a partial tail), CoW on the tail's
    first decode write, then both released — every reference freed
    exactly once, every shared write behind a copy."""
    from autodist_tpu.serving.kv_cache import BlockAllocator

    a = BlockAllocator(8)
    b0, b1, b2 = a.alloc(3)          # request A admits: 3 novel blocks
    a.note("write", b2)              # A's first decode fills the tail
    a.share(b0)                      # request B: 2 full-prefix hits...
    a.share(b1)
    a.share(b2)                      # ...plus the partial tail
    (r,) = a.alloc(1)                # B's CoW reserve for that tail
    a.note("cow", b2, r)             # B's first write: copy...
    a.free_one(b2)                   # ...drop B's ref on the shared src
    a.note("write", r)               # ...write the private replica
    a.note("write", b2)              # A keeps writing its own tail
    a.free([b0, b1, b2])             # A releases
    a.free([b0, b1, r])              # B releases
    return list(a.events)


@dataclasses.dataclass
class BlockTraceMutation:
    """Doctor an honest block-allocator event trace; the trace lint
    must fire ``code`` on the doctored replay and stay silent on the
    honest one."""

    name: str
    code: str
    description: str
    mutate: Callable  # (list[tuple]) -> list[tuple]
    kind: str = "block_trace"

    def run(self) -> dict:
        from autodist_tpu.analysis.program_rules import lint_block_trace

        events = _block_trace_fixture()
        clean = lint_block_trace(events, where=self.name)
        mutated = lint_block_trace(self.mutate(list(events)),
                                   where=self.name)
        return {"name": self.name, "kind": self.kind, "code": self.code,
                "clean_ok": self.code not in clean.codes(),
                "fired": self.code in mutated.codes(),
                "description": self.description}


def _block_trace_mutations() -> list[BlockTraceMutation]:
    def drop_cow(t):
        # PagedLayout.protect is skipped: the copy and the ref-drop
        # vanish and the write lands on the still-shared source.
        i = t.index(("cow", 2, 3))
        return t[:i] + [("write", 2)] + t[i + 3:] \
            + [("free", 2), ("free", 3)]

    def double_free(t):
        # release_slot runs twice for the same request (the failover /
        # hedging-loser race the chaos matrix hunts).
        return t + [("free", 0), ("free", 1)]

    def stale_write(t):
        # a decode write lands after the slot released its blocks.
        return t + [("write", 2)]

    return [
        BlockTraceMutation(
            "shared_block_written_without_cow", "ADT116",
            "the copy-on-write step is skipped — a decode write lands "
            "on a refcount-2 shared prefix block and the other "
            "holder's cached tokens silently change",
            drop_cow),
        BlockTraceMutation(
            "pool_block_double_freed", "ADT117",
            "a request's blocks are freed twice (the failover / "
            "hedge-loser double-release) — the pool would hand a "
            "still-mapped physical block to the next admission",
            double_free),
        BlockTraceMutation(
            "stale_table_entry_written", "ADT116",
            "a decode write lands through a table entry whose block "
            "was already released (stale mapping outliving the slot)",
            stale_write),
    ]


def _reshard_mutations() -> list[ReshardMutation]:
    def drop_leaf(src, dst):
        dst["leaves"].pop("params/b")
        return src, dst

    def flip_dtype(src, dst):
        dst["leaves"]["params/w"]["dtype"] = "bfloat16"
        return src, dst

    def flip_shape(src, dst):
        dst["leaves"]["params/w"]["logical_shape"][0] += 1
        return src, dst

    def orphan_sync(src, dst):
        src["sync"]["sync_state/g0:bf16_ef"] = {
            "rows": 8, "width": 16, "compressor": "bf16_ef"}
        src["leaves"]["sync_state/g0:bf16_ef"] = {
            "stored_shape": [8, 16], "logical_shape": [8, 16],
            "dtype": "float32", "ops": []}
        return src, dst

    return [
        ReshardMutation(
            "reshard_leaf_dropped", "ADT070",
            "a target state leaf vanishes (different optimizer / "
            "edited sidecar) — coded error, not a mid-reshard tree "
            "error", drop_leaf),
        ReshardMutation(
            "reshard_dtype_flipped", "ADT070",
            "source/target logical dtypes disagree on one leaf",
            flip_dtype),
        ReshardMutation(
            "reshard_shape_flipped", "ADT070",
            "source/target logical shapes disagree on one leaf",
            flip_shape),
        ReshardMutation(
            "reshard_ef_state_dropped", "ADT071",
            "source error-feedback rows have no home in the target "
            "layout (re-seeded, warned)", orphan_sync),
    ]


def _set_node(d: dict, suffix: str, **updates) -> dict:
    """Update the first node config whose var_name ends with suffix."""
    for nc in d["node_configs"]:
        if nc["var_name"].endswith(suffix):
            for key, value in updates.items():
                obj, _, field = key.partition(".")
                if field:
                    nc[obj][field] = value
                else:
                    nc[obj] = value
            return d
    raise KeyError(f"no node config matching {suffix!r}")


# --------------------------------------------------------------------------- #
# The plan-mutation matrix
# --------------------------------------------------------------------------- #
def _plan_mutations() -> list[PlanMutation]:
    def edit(fn):
        def apply(d):
            fn(d)
            return d
        return apply

    return [
        PlanMutation(
            "mesh_product_broken", "ADT001",
            "hand-edited mesh_axes no longer cover the device count",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: d["graph_config"]["mesh_axes"].update(
                {"data": 4}))),
        PlanMutation(
            "replicas_drifted", "ADT002",
            "graph replicas disagree with the mesh data axes",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: d["graph_config"].update({"replicas": 4}))),
        PlanMutation(
            "unknown_lowering", "ADT003",
            "lowering kind nobody implements",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: d["graph_config"].update(
                {"lowering": "magic"}))),
        PlanMutation(
            "lowering_axis_missing", "ADT004",
            "lowering re-pointed at a backend whose mesh axis the "
            "topology lacks",
            _fsdp_fixture,
            edit(lambda d: d["graph_config"].update(
                {"lowering": "sequence"}))),
        PlanMutation(
            "tp_exceeds_model_axis", "ADT005",
            "tensor_parallel raised beyond the model axis",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: d["graph_config"]["parallel"].update(
                {"tensor_parallel": 4}))),
        PlanMutation(
            "spec_names_missing_axis", "ADT006",
            "partitioner spec names a mesh axis the mesh lacks",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: _set_node(
                d, "mlp/wi/kernel",
                **{"partitioner.spec": ["pipe", None, "megamodel"]}))),
        PlanMutation(
            "microbatches_zeroed", "ADT007",
            "pipeline schedule knob edited out of range",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: d["graph_config"]["parallel"].update(
                {"num_microbatches": 0}))),
        PlanMutation(
            "orphan_precision_slot", "ADT020",
            "tp_psum narrowing requested on a plan with no tp boundary",
            lambda: _pipeline_fixture(),
            edit(lambda d: d["graph_config"].update(
                {"precision": {"tp_psum": "int8"}}))),
        PlanMutation(
            "per_var_precision_disagreement", "ADT021",
            "hand-edited per-variable precisions disagree in one slot",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: (
                _set_node(d, "mlp/wi/kernel",
                          **{"partitioner.precision": "int8"}),
                _set_node(d, "mlp/wo/kernel",
                          **{"partitioner.precision": "bf16"})))),
        PlanMutation(
            "per_var_precision_contradicts_graph", "ADT022",
            "per-variable record contradicts the graph policy slot",
            lambda: _pipeline_fixture(tensor_parallel=2,
                                      collective_precision={
                                          "tp_psum": "int8"}),
            edit(lambda d: _set_node(
                d, "mlp/wi/kernel",
                **{"partitioner.precision": "bf16"}))),
        PlanMutation(
            "grad_precision_vs_compressor", "ADT023",
            "grad precision slot plus a pinned non-EF compressor",
            lambda: _pipeline_fixture(tensor_parallel=2,
                                      collective_precision={
                                          "grad": "int8"}),
            edit(lambda d: _set_node(
                d, "mlp/wi/kernel",
                **{"synchronizer.compressor": "fp16"}))),
        PlanMutation(
            "zero_rereplicated_onto_tp_shard", "ADT030",
            "ZeRO request hand-added to a tensor-parallel-sharded "
            "variable (state already shards with the parameter)",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: _set_node(
                d, "mlp/wi/kernel",
                synchronizer={"kind": "ps", "zero_stage": 3,
                              "reduction_destination": "",
                              "local_replication": False, "sync": True,
                              "staleness": 0}))),
        PlanMutation(
            "zero3_on_vocab_table", "ADT031",
            "zero_stage=3 hand-added to the model-sharded table",
            lambda: _pipeline_fixture(tensor_parallel=2,
                                      vocab_parallel=True),
            edit(lambda d: _set_node(
                d, "shared/embedding",
                synchronizer={"kind": "ps", "zero_stage": 3,
                              "reduction_destination": "",
                              "local_replication": False, "sync": True,
                              "staleness": 0}))),
        PlanMutation(
            "zero_stage_out_of_range", "ADT032",
            "hand-edited ZeRO stage outside the ladder",
            lambda: _pipeline_fixture(tensor_parallel=2, zero_stage=3),
            edit(lambda d: _set_node(
                d, "ln_mlp/scale", **{"synchronizer.zero_stage": 7}))),
        PlanMutation(
            "gspmd_zero_stage3", "ADT033",
            "stage 3 hand-edited under the gspmd lowering",
            _fsdp_fixture,
            edit(lambda d: _set_node(
                d, "w",
                synchronizer={"kind": "ps", "zero_stage": 3,
                              "reduction_destination": "",
                              "local_replication": False, "sync": True,
                              "staleness": 0}))),
        PlanMutation(
            "lowering_degraded_zero", "ADT034",
            "the lowering recorded a warn-and-degrade (surfaced "
            "through the one shared diagnostics path)",
            lambda: _pipeline_fixture(tensor_parallel=2),
            lambda d: d,
            lowered_factory=lambda: SimpleNamespace(zero_degraded={
                "stages/mlp/wi/kernel":
                    "ZeRO on a tp-sharded variable is a no-op request"})),
        PlanMutation(
            "comm_overlap_disagreement", "ADT040",
            "per-variable overlap modes disagree with no graph knob",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: (
                d["graph_config"]["parallel"].update(
                    {"comm_overlap": None}),
                _set_node(d, "mlp/wi/kernel",
                          **{"partitioner.comm_overlap": "rsag"}),
                _set_node(d, "mlp/wo/kernel",
                          **{"partitioner.comm_overlap": "matmul"})))),
        PlanMutation(
            "comm_overlap_contradicts_graph", "ADT041",
            "per-variable overlap contradicts the graph knob",
            lambda: _pipeline_fixture(tensor_parallel=2,
                                      comm_overlap="rsag"),
            edit(lambda d: _set_node(
                d, "mlp/wi/kernel",
                **{"partitioner.comm_overlap": "matmul"}))),
        PlanMutation(
            "overlap_noop_at_tp1", "ADT042",
            "comm_overlap recorded on a tp=1 plan (silent no-op)",
            lambda: _pipeline_fixture(),
            edit(lambda d: d["graph_config"]["parallel"].update(
                {"comm_overlap": "rsag"}))),
        PlanMutation(
            "vocab_noop_at_tp1", "ADT043",
            "vocab_parallel recorded on a tp=1 plan (silent no-op)",
            lambda: _pipeline_fixture(),
            edit(lambda d: d["graph_config"]["parallel"].update(
                {"vocab_parallel": True}))),
        PlanMutation(
            "unknown_overlap_mode", "ADT044",
            "comm_overlap mode nobody implements",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: d["graph_config"]["parallel"].update(
                {"comm_overlap": "ring"}))),
        PlanMutation(
            "tp_sharded_across_dcn", "ADT060",
            "a stage variable's spec hand-edited to shard over the "
            "cross-slice dcn axis (model collectives riding DCN)",
            _multislice_fixture,
            edit(lambda d: _set_node(
                d, "mlp/wi/kernel",
                **{"partitioner.spec": ["pipe", "dcn", None]}))),
        PlanMutation(
            "compressor_without_data_axis", "ADT051",
            "compressor hand-added on a pipe-only mesh (no data axis "
            "to compress over)",
            _pipe_only_fixture,
            edit(lambda d: _set_node(
                d, "ln_mlp/scale",
                **{"synchronizer.compressor": "bf16_ef"}))),
        PlanMutation(
            "unknown_compressor", "ADT050",
            "compressor name outside the registry",
            lambda: _pipeline_fixture(tensor_parallel=2),
            edit(lambda d: _set_node(
                d, "ln_mlp/scale",
                **{"synchronizer.compressor": "wavelet"}))),
        PlanMutation(
            "kernel_enabling_knob_dropped", "ADT090",
            "the precision policy hand-stripped from a quant_ring-"
            "elected plan (the fused ring would silently never run)",
            lambda: _pipeline_fixture(
                tensor_parallel=2,
                collective_precision={"tp_psum": "int8"},
                kernel=("quant_ring",)),
            edit(lambda d: d["graph_config"].update({"precision": {}}))),
        PlanMutation(
            "moe_a2a_orphaned", "ADT020",
            "moe_a2a narrowing hand-added to a 1-expert-degree plan "
            "(no dispatch/combine wire exists to narrow)",
            lambda: _expert_fixture(mesh={"data": 4, "expert": 1}),
            edit(lambda d: d["graph_config"].update(
                {"precision": {"moe_a2a": "int8"}}))),
        PlanMutation(
            "a2a_ring_policy_stripped", "ADT090",
            "the moe_a2a policy hand-stripped from an a2a_ring-elected "
            "plan (the fused dispatch/combine ring would silently "
            "never run)",
            lambda: _expert_fixture(
                collective_precision={"moe_a2a": "int8"},
                kernel=("a2a_ring",)),
            edit(lambda d: d["graph_config"].update({"precision": {}}))),
        PlanMutation(
            "a2a_ring_pushed_over_dcn", "ADT090",
            "expert_over_dcn hand-added to an a2a_ring-elected plan "
            "(the ICI ppermute ring cannot span slices)",
            lambda: _expert_fixture(
                collective_precision={"moe_a2a": "int8"},
                kernel=("a2a_ring",)),
            edit(lambda d: d["graph_config"]["parallel"].update(
                {"expert_over_dcn": True}))),
        PlanMutation(
            "expert_pushed_over_dcn", "ADT061",
            "expert placement hand-flipped across the slice boundary "
            "(every dispatch/combine a2a rides DCN; warns, never "
            "prunes — the search may elect it on merit)",
            lambda: _expert_fixture(),
            edit(lambda d: d["graph_config"]["parallel"].update(
                {"expert_over_dcn": True}))),
    ]


# --------------------------------------------------------------------------- #
# The program-mutation matrix
# --------------------------------------------------------------------------- #
def _inject(line: str):
    def apply(text: str) -> str:
        head, sep, tail = text.partition("ENTRY ")
        return head + line + "\n" + sep + tail
    return apply


def _program_mutations() -> list[ProgramMutation]:
    P = programs
    tp_only = (("tp_psum", "int8"),)
    moe_only = (("moe_a2a", "int8"),)
    T = P.DEC_T
    lane = P.DEC_SLOTS * 1 * T * P.DEC_HEAD_DIM
    min_gathers = P.Z3_V * P.Z3_LEAVES
    # The pipeline-corpus vocab geometry (distinctive V, tp=2 padding)
    PIPE_V = 93
    PIPE_V_PAD = PIPE_V + (-PIPE_V) % 2

    def tp1_ars():
        return collective_counts(P.pipeline_step_text(1))["all-reduce"]

    return [
        ProgramMutation(
            "host_transfer_injected", "ADT101",
            "a send() appears inside the step program",
            lambda: P.tiny_step_text(2),
            lambda: [R.no_host_transfer()],
            _inject("  %ht = f32[8]{0} send(f32[8]{0} %x, token[] %tk), "
                    "channel_id=1")),
        ProgramMutation(
            "decode_window_unrolled", "ADT102",
            "the K-token decode window loses its fused while loop",
            lambda: P.decode_step_text(2, True),
            lambda: [R.fused_loop()],
            lambda t: t.replace(" while(", " unrolled(")
                       .replace("while (", "unrolled (")),
        ProgramMutation(
            "donation_alias_dropped", "ADT103",
            "the donated KV cache loses its input/output aliasing",
            lambda: P.decode_step_text(2, True),
            lambda: [R.donated_alias()],
            lambda t: t.replace("input_output_alias", "io_alias_gone")),
        ProgramMutation(
            "cache_lane_copy_injected", "ADT104",
            "a cache-lane-sized copy appears per dispatch "
            "(copy-on-write regression)",
            lambda: P.decode_step_text(2, True),
            lambda: [R.no_donated_copy(T, lane, "cache-lane")],
            _inject(f"  %cp = f32[{P.DEC_SLOTS},1,{T},{P.DEC_HEAD_DIM}]"
                    "{3,2,1,0} copy(f32"
                    f"[{P.DEC_SLOTS},1,{T},{P.DEC_HEAD_DIM}]"
                    "{2,3,1,0} %cache)")),
        ProgramMutation(
            "vocab_shard_rereplicated", "ADT105",
            "the vocab-sharded loss head re-replicates (the program a "
            "dropped spec would compile to)",
            lambda: P.pipeline_step_text(2, vocab_parallel=True,
                                         vocab_size=PIPE_V),
            lambda: [R.no_buffer_with_dim((PIPE_V, PIPE_V_PAD),
                                          "vocab")],
            lambda t: P.pipeline_step_text(2, vocab_size=PIPE_V)),
        ProgramMutation(
            "zero3_boundary_rematerialized", "ADT106",
            "full parameters re-appear across the step boundary (the "
            "program a dropped ZeRO-3 spec would compile to)",
            lambda: P.zero_step_text(3),
            lambda: [R.sharded_step_boundary(P.Z3_DIM)],
            lambda t: P.zero_step_text(0)),
        ProgramMutation(
            "zero3_gathers_bulk_collapsed", "ADT107",
            "the per-layer gather chain collapses into a bulk "
            "materialization",
            lambda: P.zero_step_text(3),
            lambda: [R.min_collectives("all-gather", min_gathers,
                                       "per-layer ZeRO-3 gathers")],
            lambda t: t.replace("all-gather", "bulk-gather")),
        ProgramMutation(
            "refusion_barrier_removed", "ADT108",
            "the rs+ag re-fusion barrier is removed (the blocking "
            "program XLA would re-fuse to)",
            lambda: P.pipeline_step_text(2, comm_overlap="rsag",
                                         collective_precision=tp_only),
            lambda: [R.no_refused_pair(
                nonscalar_all_reduces(P.pipeline_step_text(1)),
                payload_only=True)],
            lambda t: P.pipeline_step_text(2)),
        ProgramMutation(
            "precision_policy_dropped", "ADT109",
            "an int8-policied boundary compiles to an fp32 wire (the "
            "program a dropped policy would compile to)",
            lambda: P.pipeline_step_text(
                2, collective_precision=tp_only),
            lambda: [R.quantized_wire(mins={"all-reduce": 4})],
            lambda t: P.pipeline_step_text(2)),
        ProgramMutation(
            "unpolicied_boundary_narrowed", "ADT109",
            "an fp32-policy program silently narrows a wire",
            lambda: P.pipeline_step_text(2),
            lambda: [R.quantized_wire(clean=True)],
            lambda t: P.pipeline_step_text(
                2, collective_precision=tp_only)),
        ProgramMutation(
            "full_array_gather", "ADT110",
            "an all-gather materializes a full array where the plan "
            "promises shards",
            lambda: P.zero_step_text(3),
            lambda: [R.no_full_gather(10 ** 5)],
            _inject("  %fg = f32[1000000]{0} all-gather(f32[500000]{0} "
                    "%p), dimensions={0}")),
        ProgramMutation(
            "reshard_full_gather", "ADT110",
            "a reshard program stages through full-array "
            "materialization (the program a gather-to-replicated "
            "route compiles to) instead of shard-to-shard collective "
            "routes",
            lambda: P.reshard_step_text(),
            lambda: R.rules_for_reshard(P.reshard_budget()),
            lambda t: P.reshard_step_text(naive=True)),
        ProgramMutation(
            "kv_write_scatterized", "ADT111",
            "the in-place KV write lowers to something other than "
            "dynamic-update-slice",
            lambda: P.decode_step_text(2, True),
            lambda: [R.min_dus(2 * P.DEC_LAYERS)],
            lambda t: t.replace("dynamic-update-slice",
                                "dynamic-overwrite")),
        ProgramMutation(
            "score_square_materialized", "ADT112",
            "a [T, T] attention-score square appears in a single-token "
            "step",
            lambda: P.decode_step_text(2, True),
            lambda: [R.no_score_square(T)],
            _inject(f"  %sq = f32[3,2,{T},{T}]{{3,2,1,0}} multiply("
                    f"f32[3,2,{T},{T}]{{3,2,1,0}} %a, "
                    f"f32[3,2,{T},{T}]{{3,2,1,0}} %b)")),
        ProgramMutation(
            "single_replica_collective", "ADT113",
            "a cross-device collective appears in a 1-device program",
            lambda: P.tiny_step_text(1),
            lambda: [R.no_collectives()],
            _inject("  %ar = f32[8]{0} all-reduce(f32[8]{0} %g), "
                    "replica_groups={}, to_apply=%add")),
        ProgramMutation(
            "quant_ring_kernel_dropped", "ADT120",
            "the s8 EQuARX ring goes missing (the composed int8 "
            "convert-sandwich program a dropped kernel slot compiles "
            "to)",
            lambda: P.pipeline_step_text(2, collective_precision=tp_only,
                                         kernel=("quant_ring",)),
            lambda: [R.fused_kernel_replaced(("quant_ring",), tp=2)],
            lambda t: P.pipeline_step_text(
                2, collective_precision=tp_only)),
        ProgramMutation(
            "collective_matmul_kernel_dropped", "ADT120",
            "the fused ring step goes missing (the composed "
            "collective-matmul program a dropped kernel slot compiles "
            "to)",
            lambda: P.pipeline_step_text(2, comm_overlap="matmul",
                                         kernel=("collective_matmul",)),
            lambda: [R.fused_kernel_replaced(("collective_matmul",),
                                             tp=2)],
            lambda t: P.pipeline_step_text(2, comm_overlap="matmul")),
        ProgramMutation(
            "a2a_ring_kernel_dropped", "ADT120",
            "the fused s8 dispatch/combine ring goes missing (the "
            "composed monolithic-all-to-all program a dropped kernel "
            "slot compiles to)",
            lambda: P.moe_step_text(2, moe_only,
                                    ("a2a_ring",)),
            lambda: [R.fused_kernel_replaced(("a2a_ring",), expert=2)],
            lambda t: P.moe_step_text(2, moe_only)),
        ProgramMutation(
            "moe_a2a_policy_dropped", "ADT109",
            "an int8-policied dispatch/combine boundary compiles to an "
            "fp32 all-to-all wire (the program a dropped policy would "
            "compile to)",
            lambda: P.moe_step_text(2, moe_only),
            lambda: [R.quantized_wire(mins={"all-to-all": 4})],
            lambda t: P.moe_step_text(2)),
        ProgramMutation(
            "unpolicied_moe_a2a_narrowed", "ADT109",
            "an fp32-policy MoE program silently narrows its "
            "dispatch/combine wire",
            lambda: P.moe_step_text(2),
            lambda: [R.quantized_wire(clean=True)],
            lambda t: P.moe_step_text(2, moe_only)),
        ProgramMutation(
            "paged_decode_densified", "ADT115",
            "a paged-elected decode compiles the dense [slots x "
            "max_len] reservation anyway (the program a dropped "
            "kv_layout knob compiles to)",
            lambda: P.decode_step_text(1, False, kv_layout="paged"),
            lambda: [R.paged_cache(P.DEC_SLOTS, T,
                                   pool_blocks=P.DEC_POOL_BLOCKS)],
            lambda t: P.decode_step_text(1, False)),
        ProgramMutation(
            "paged_table_gather_dropped", "ADT115",
            "the block-table gather over the KV pool goes missing "
            "(dense addressing surviving inside a paged program)",
            lambda: P.decode_step_text(1, False, kv_layout="paged"),
            lambda: [R.paged_cache(P.DEC_SLOTS, T,
                                   pool_blocks=P.DEC_POOL_BLOCKS)],
            lambda t: t.replace(" gather(", " splat(")),
        ProgramMutation(
            "flash_decode_kernel_dropped", "ADT120",
            "the flash-decode cache kernel goes missing (the composed "
            "einsum decode program a dropped kernel slot compiles to)",
            lambda: P.decode_step_text(1, False,
                                       kernel=("flash_decode",)),
            lambda: [R.fused_kernel_replaced(("flash_decode",), tp=1)],
            lambda t: P.decode_step_text(1, False)),
        ProgramMutation(
            "tp_psums_missing", "ADT114",
            "the per-stage Megatron activation all-reduces go missing "
            "(the tp=1 program presented as tp=2)",
            lambda: P.pipeline_step_text(2),
            lambda: [R.min_extra_all_reduces(
                tp1_ars(), 4, "Megatron activation all-reduces")],
            lambda t: P.pipeline_step_text(1)),
    ]


def all_mutations() -> list:
    return (_plan_mutations() + _program_mutations()
            + _reshard_mutations() + _supervision_mutations()
            + _fleet_mutations() + _disagg_mutations()
            + _handoff_mutations() + _block_trace_mutations())


def run_mutations(names=None, kinds=None) -> list[dict]:
    """Run the matrix (optionally filtered); one result record per
    mutation: ``ok`` = rule silent on the honest artifact AND fired on
    the seeded violation."""
    results = []
    for mut in all_mutations():
        if names and mut.name not in names:
            continue
        if kinds and mut.kind not in kinds:
            continue
        rec = mut.run()
        rec["ok"] = rec["clean_ok"] and rec["fired"]
        results.append(rec)
    return results
