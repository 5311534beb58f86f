"""The Pallas fused-kernel tier (PR 13).

Interpreter-mode goldens for all three kernels against their composed
lowerings across edge shapes (non-divisible block sizes, slot lengths
shorter than one block, V % tp != 0 vocab padding, all-zero quantize
blocks), the Strategy-IR kernel-slot round trip (pre-PR-13 JSON lowers
byte-identically with the slot absent), both-directions election per
link/kernel profile (training search AND serving decode), the serving
engine's attention_fn gate, the ADT090/ADT120 rules, and the telemetry
kernel-gauge schema gate.

Kernel modules are imported inside tests (conftest guard: Pallas
modules are never top-level imports in a tier-1 module); shapes stay
tiny so the interpreter runs in seconds.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu import AutoDist
from autodist_tpu.resource import ResourceSpec
from autodist_tpu.strategy.ir import (Strategy, UnknownKernelError,
                                      normalize_kernel)

TP_SPEC = {"topology": {"platform": "cpu", "num_devices": 8},
           "mesh": {"data": 2, "pipe": 2, "model": 2}}


def _lm_cfg(**kw):
    from autodist_tpu.models.transformer import TransformerConfig

    base = dict(vocab_size=32, hidden_size=16, num_layers=2,
                num_heads=2, mlp_dim=32, max_len=8, dtype=jnp.float32,
                dropout_rate=0.0, attention_dropout_rate=0.0)
    base.update(kw)
    return TransformerConfig(**base)


def _lm_trainable(cfg):
    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable

    return make_pipeline_lm_trainable(cfg, optax.sgd(0.05),
                                      jax.random.PRNGKey(0))


def _lm_batch(vocab, batch=8, length=8, seed=0):
    r = np.random.RandomState(seed)
    return {"x": r.randint(0, vocab, (batch, length)).astype(np.int32),
            "y": r.randint(0, vocab, (batch, length)).astype(np.int32)}


# --------------------------------------------------------------------------- #
# Kernel goldens vs the composed lowerings (interpreter mode)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("lengths,block_k,T", [
    ([0, 3, 56], 16, 64),      # slot shorter than one block + near-full
    ([1, 15, 16], 16, 64),     # block-boundary edges
    ([55, 2, 30], 13, 57),     # a lane of 57 does not divide by 13
])
def test_flash_decode_golden_vs_cached_attention(lengths, block_k, T):
    from autodist_tpu.kernel.pallas.flash_decode import \
        flash_decode_attention_dense
    from autodist_tpu.serving.kv_cache import cached_attention

    B, H, d = 3, 2, 8
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, 1, H, d), jnp.float32)
    k = jnp.asarray(r.randn(B, H, T, d), jnp.float32)
    v = jnp.asarray(r.randn(B, H, T, d), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    if T % block_k:
        # the dense entry takes the cache as it is: no padded copy
        with pytest.raises(ValueError, match="does not divide into blocks"):
            flash_decode_attention_dense(q, k[None], v[None], 0, lens,
                                         block_k=block_k)
        return
    ref = cached_attention(q, k, v, lens)
    got = flash_decode_attention_dense(q, k[None], v[None], 0, lens,
                                       block_k=block_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_attention_past_its_vmem_bound_is_a_value_error():
    """The training flash kernel holds whole-sequence K/V blocks in
    VMEM; a sequence past the longest that compiles on the chip raises
    here, naming the bound, instead of an OOM from the compiler."""
    from autodist_tpu.ops.flash_attention import (MAX_SEQ_BYTES,
                                                  flash_attention)

    q = jnp.zeros((1, 2 * MAX_SEQ_BYTES // 2, 1, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="longest that compiles, 16384"):
        flash_attention(q, q, q)
    q32 = jnp.zeros((1, MAX_SEQ_BYTES // 2, 1, 64), jnp.float32)
    with pytest.raises(ValueError, match="longest that compiles, 8192"):
        flash_attention(q32, q32, q32)


@pytest.mark.parametrize("n,size", [(2, 37), (4, 64), (2, 8)])
def test_quant_ring_golden(n, size):
    """The fused-q/dq ring reproduces its arithmetic mirror (per-hop
    requantization included) and stays within int8 tolerance of the
    exact fp32 sum; payload sizes that don't divide the ring exercise
    the zero-pad path."""
    from autodist_tpu.kernel.pallas.quant_ring import (
        quantized_ring_all_reduce, reference_ring_all_reduce)

    mesh = Mesh(np.array(jax.devices()[:n]), ("model",))
    r = np.random.RandomState(0)
    xs = jnp.asarray(r.randn(n, size), jnp.float32)
    sm = jax.jit(jax.shard_map(
        lambda x: quantized_ring_all_reduce(x, "model"), mesh=mesh,
        in_specs=P("model"), out_specs=P("model"), check_vma=False))
    got = sm(xs)
    refs = reference_ring_all_reduce(list(xs))
    for i in range(n):
        np.testing.assert_allclose(np.asarray(got[i]),
                                   np.asarray(refs[i]), atol=1e-6)
    true_sum = np.asarray(jnp.sum(xs, 0))
    scale = np.abs(true_sum).max()
    for i in range(n):
        np.testing.assert_allclose(np.asarray(got[i]), true_sum,
                                   atol=0.1 * scale)


def test_quant_ring_all_zero_block():
    from autodist_tpu.kernel.pallas.quant_ring import \
        quantized_ring_all_reduce

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    xs = jnp.zeros((2, 16), jnp.float32)
    sm = jax.jit(jax.shard_map(
        lambda x: quantized_ring_all_reduce(x, "model"), mesh=mesh,
        in_specs=P("model"), out_specs=P("model"), check_vma=False))
    assert float(jnp.max(jnp.abs(sm(xs)))) == 0.0


@pytest.mark.parametrize("xs,ks,axes,specs", [
    ((4, 6), (6, 10), 1, (P(None, "model"), P("model", None))),
    ((4, 6), (6, 16), 1, (P(None, "model"), P("model", None))),
    # axes=2 (the attention out-proj shape) with width 7 % tp != 0
    ((4, 2, 4), (2, 4, 7), 2,
     (P(None, "model", None), P("model", None, None))),
])
def test_collective_matmul_fused_golden(xs, ks, axes, specs):
    """Fused ring step == composed collective_matmul_row bit-for-bit
    (same arithmetic, one kernel pass), gradients included."""
    from autodist_tpu.kernel.pallas.collective_matmul import \
        collective_matmul_row_fused
    from autodist_tpu.parallel.tensor import collective_matmul_row

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(*xs), jnp.float32)
    kern = jnp.asarray(r.randn(*ks), jnp.float32)

    def run(fn):
        def g(xl, kl):
            return fn(xl, kl, "model", axes)
        return jax.jit(jax.shard_map(g, mesh=mesh, in_specs=specs,
                                     out_specs=P(), check_vma=False))

    comp = run(collective_matmul_row)(x, kern)
    fused = run(collective_matmul_row_fused)(x, kern)
    np.testing.assert_array_equal(np.asarray(comp), np.asarray(fused))

    def grads(fn):
        def g(xl, kl):
            return fn(xl, kl, "model", axes)
        sm = jax.shard_map(g, mesh=mesh, in_specs=specs, out_specs=P(),
                           check_vma=False)
        return jax.jit(jax.grad(lambda a, b: jnp.sum(sm(a, b) ** 2),
                                argnums=(0, 1)))(x, kern)

    for a, b in zip(grads(collective_matmul_row),
                    grads(collective_matmul_row_fused)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------- #
# Strategy IR: the kernel slot
# --------------------------------------------------------------------------- #
def test_normalize_kernel_forms_and_rejects():
    assert normalize_kernel(None) == {}
    assert normalize_kernel({}) == {}
    assert normalize_kernel("quant_ring") == {"quant_ring": True}
    assert normalize_kernel(("collective_matmul", "flash_decode")) == {
        "flash_decode": True, "collective_matmul": True}
    assert normalize_kernel({"quant_ring": False}) == {}
    with pytest.raises(UnknownKernelError):
        normalize_kernel("warp_drive")
    with pytest.raises(UnknownKernelError):
        Strategy.from_json(json.dumps({
            "id": "x", "node_configs": [],
            "graph_config": {"kernel": {"warp_drive": True}}}))


def test_kernel_slot_round_trips_and_pre_pr13_json_is_composed():
    from autodist_tpu.strategy.parallel_builders import Pipeline

    cfg = _lm_cfg()
    tr = _lm_trainable(cfg)
    spec = ResourceSpec(TP_SPEC)
    s = Pipeline(num_microbatches=2, tensor_parallel=2,
                 collective_precision={"tp_psum": "int8"},
                 kernel=("quant_ring",)).build(tr, spec)
    clone = Strategy.from_json(s.to_json())
    assert clone.graph_config.kernel == {"quant_ring": True}
    # A pre-PR-13 JSON (no kernel key at all) deserializes to the
    # composed lowering.
    d = json.loads(s.to_json())
    del d["graph_config"]["kernel"]
    old = Strategy.from_json(json.dumps(d))
    assert old.graph_config.kernel == {}


def test_pre_pr13_json_lowers_byte_identically():
    """Stripping the (empty) kernel slot from a serialized strategy
    changes nothing about the compiled program — the slot is additive."""
    from autodist_tpu.strategy.parallel_builders import Pipeline

    cfg = _lm_cfg()
    tr = _lm_trainable(cfg)
    spec = ResourceSpec(TP_SPEC)
    s = Pipeline(num_microbatches=2, tensor_parallel=2).build(tr, spec)
    d = json.loads(s.to_json())
    assert d["graph_config"]["kernel"] == {}
    del d["graph_config"]["kernel"]
    old = Strategy.from_json(json.dumps(d))
    batch = _lm_batch(cfg.vocab_size)

    def text_of(strategy):
        runner = AutoDist(TP_SPEC, "AllReduce").build(tr, strategy)
        try:
            return runner.lowered.step_fn.lower(
                runner.state, runner._place_batch(batch),
                jax.random.PRNGKey(0)).compile().as_text()
        finally:
            runner.close()

    assert _without_locations(text_of(s)) == \
        _without_locations(text_of(old))


def _without_locations(hlo: str) -> str:
    """HLO text minus what depends on where in the source the program
    was traced from: the file/function/location/frame tables and each
    op's frame id (two ``text_of`` calls sit at different columns)."""
    import re

    hlo = re.sub(r"\n(?:FileNames|FunctionNames|FileLocations|"
                 r"StackFrames)\n(?:\d+ .*\n)*", "\n", hlo)
    return re.sub(r" ?stack_frame_id=\d+", "", hlo)


def test_builder_rejects_kernel_without_enabling_knob():
    from autodist_tpu.strategy.parallel_builders import Pipeline

    with pytest.raises(ValueError, match="quant_ring"):
        Pipeline(tensor_parallel=2, kernel=("quant_ring",))
    with pytest.raises(ValueError, match="quant_ring"):
        Pipeline(tensor_parallel=2,
                 collective_precision={"tp_psum": "int8"},
                 comm_overlap="rsag", kernel=("quant_ring",))
    with pytest.raises(ValueError, match="collective_matmul"):
        Pipeline(tensor_parallel=2, kernel=("collective_matmul",))


def test_plan_lint_adt090_fires_on_hand_edit_and_stays_silent():
    from autodist_tpu.analysis import lint_plan
    from autodist_tpu.strategy.parallel_builders import Pipeline

    cfg = _lm_cfg()
    tr = _lm_trainable(cfg)
    spec = ResourceSpec(TP_SPEC)
    s = Pipeline(num_microbatches=2, tensor_parallel=2,
                 collective_precision={"tp_psum": "int8"},
                 kernel=("quant_ring",)).build(tr, spec)
    clean = lint_plan(s, resource_spec=spec, trainable=tr)
    assert "ADT090" not in clean.codes()
    d = json.loads(s.to_json())
    d["graph_config"]["precision"] = {}
    mutated = lint_plan(Strategy.from_json(json.dumps(d)),
                        resource_spec=spec, trainable=tr)
    assert "ADT090" in mutated.codes()


# --------------------------------------------------------------------------- #
# Training goldens: kernel-elected steps track the composed siblings
# --------------------------------------------------------------------------- #
def _train_losses(tr_factory, batch, steps=3, **autodist_kw):
    runner = AutoDist(TP_SPEC, "Pipeline", num_microbatches=2,
                      **autodist_kw).build(tr_factory())
    try:
        return [float(np.asarray(runner.step(batch)["loss"]))
                for _ in range(steps)]
    finally:
        runner.close()


def test_quant_ring_training_tracks_composed_int8():
    """The ring-elected trajectory stays within the int8-vs-composed
    tolerance of the composed int8 program (per-hop requantization is
    the only numeric difference)."""
    cfg = _lm_cfg()
    batch = _lm_batch(cfg.vocab_size)
    make = lambda: _lm_trainable(cfg)   # noqa: E731
    composed = _train_losses(make, batch, tensor_parallel=2,
                             collective_precision={"tp_psum": "int8"})
    ring = _train_losses(make, batch, tensor_parallel=2,
                         collective_precision={"tp_psum": "int8"},
                         kernel=("quant_ring",))
    np.testing.assert_allclose(ring, composed, rtol=2e-2)


def test_collective_matmul_training_matches_composed():
    """The fused ring step is the same arithmetic — trajectories are
    bit-close to the composed matmul-overlap program."""
    cfg = _lm_cfg()
    batch = _lm_batch(cfg.vocab_size)
    make = lambda: _lm_trainable(cfg)   # noqa: E731
    composed = _train_losses(make, batch, tensor_parallel=2,
                             comm_overlap="matmul")
    fused = _train_losses(make, batch, tensor_parallel=2,
                          comm_overlap="matmul",
                          kernel=("collective_matmul",))
    np.testing.assert_allclose(fused, composed, rtol=1e-5)


def test_quant_ring_with_vocab_padding():
    """V % tp != 0: the vocab-parallel prologue's lookup psum rides the
    ring too (it IS a sum_partials boundary) over zero-padded rows."""
    cfg = _lm_cfg(vocab_size=33)
    batch = _lm_batch(33)
    make = lambda: _lm_trainable(cfg)   # noqa: E731
    composed = _train_losses(make, batch, tensor_parallel=2,
                             vocab_parallel=True,
                             collective_precision={"tp_psum": "int8"})
    ring = _train_losses(make, batch, tensor_parallel=2,
                         vocab_parallel=True,
                         collective_precision={"tp_psum": "int8"},
                         kernel=("quant_ring",))
    np.testing.assert_allclose(ring, composed, rtol=2e-2)


# --------------------------------------------------------------------------- #
# ADT120: the fused-kernel program proof (both ways)
# --------------------------------------------------------------------------- #
def test_adt120_discriminates_ring_program_from_composed_sibling():
    from autodist_tpu.analysis import lint_program, programs
    from autodist_tpu.analysis.program_rules import fused_kernel_replaced

    honest = programs.pipeline_step_text(
        2, collective_precision=(("tp_psum", "int8"),),
        kernel=("quant_ring",))
    sibling = programs.pipeline_step_text(
        2, collective_precision=(("tp_psum", "int8"),))
    rules = [fused_kernel_replaced(("quant_ring",), tp=2)]
    assert not lint_program(honest, rules).errors
    assert lint_program(sibling, rules).by_code("ADT120")


def test_adt120_discriminates_flash_decode_from_composed_sibling():
    from autodist_tpu.analysis import lint_program, programs
    from autodist_tpu.analysis.program_rules import fused_kernel_replaced

    honest = programs.decode_step_text(1, False,
                                       kernel=("flash_decode",))
    sibling = programs.decode_step_text(1, False)
    rules = [fused_kernel_replaced(("flash_decode",), tp=1)]
    assert not lint_program(honest, rules).errors
    assert lint_program(sibling, rules).by_code("ADT120")


def test_adt120_holds_on_honest_tp4_ring():
    """Regression: the ring kernels drive their hops with an unrolled
    python loop, NOT lax.scan — a scanned ring prints each ppermute
    once inside an HLO while loop, so at tp >= 4 (where the trip count
    survives loop simplification) ADT120's 2(tp-1) s8-permute evidence
    would falsely report the wire missing on a program where the
    kernel genuinely ran."""
    from autodist_tpu.analysis import lint_program
    from autodist_tpu.analysis.program_rules import fused_kernel_replaced
    from autodist_tpu.analysis.programs import compiled_text
    from autodist_tpu.strategy.parallel_builders import Pipeline

    cfg = _lm_cfg(num_heads=4)
    spec = ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 8},
                         "mesh": {"data": 1, "pipe": 2, "model": 4}})
    batch = _lm_batch(cfg.vocab_size)
    auto = AutoDist(spec, Pipeline(
        num_microbatches=2, tensor_parallel=4,
        collective_precision={"tp_psum": "int8"},
        kernel=("quant_ring",)))
    runner = auto.build(_lm_trainable(cfg))
    try:
        honest = compiled_text(runner.lowered.step_fn, runner.state,
                               runner._place_batch(batch),
                               jax.random.PRNGKey(0))
    finally:
        runner.close()
    res = lint_program(honest,
                       [fused_kernel_replaced(("quant_ring",), tp=4)])
    assert not res.errors, res.errors


# --------------------------------------------------------------------------- #
# Election: the search picks a kernel exactly when the profile favors it
# --------------------------------------------------------------------------- #
def _ring_strategies():
    from autodist_tpu.strategy.parallel_builders import Pipeline

    cfg = _lm_cfg()
    tr = _lm_trainable(cfg)
    tr.tokens_per_step = 64 * 512          # comm-heavy activation hint
    spec = ResourceSpec(TP_SPEC)
    composed = Pipeline(num_microbatches=2, tensor_parallel=2,
                        collective_precision={"tp_psum": "int8"}
                        ).build(tr, spec)
    ring = Pipeline(num_microbatches=2, tensor_parallel=2,
                    collective_precision={"tp_psum": "int8"},
                    kernel=("quant_ring",)).build(tr, spec)
    return tr, spec, composed, ring


def test_quant_ring_election_pinned_both_directions():
    from autodist_tpu.simulator.cost_model import CostModel

    tr, spec, composed, ring = _ring_strategies()
    # Comm-bound: a slow wire makes the 2x byte saving dominate the
    # extra q/dq passes — the ring must win.
    slow = CostModel(spec, link_profile={"ici_gbps": 0.05},
                     quant_profile={"int8_s_per_elem": 1e-12})
    assert slow.strategy_cost(tr, ring).comm_time_s \
        < slow.strategy_cost(tr, composed).comm_time_s
    # Compute-bound: a fast wire with expensive per-hop requantization
    # flips it — the composed sandwich must win.
    fast = CostModel(spec, link_profile={"ici_gbps": 1e5},
                     quant_profile={"int8_s_per_elem": 1e-7},
                     kernel_profile={"quant_ring_qdq_factor": 4.0})
    assert fast.strategy_cost(tr, ring).comm_time_s \
        > fast.strategy_cost(tr, composed).comm_time_s


def test_search_elects_kernel_candidate_exactly_when_favored():
    """AutoStrategy(search=True)'s frontier (search_strategies is the
    engine under it) ranks a kernel-backed candidate first exactly when
    the calibrated profile favors it — pinned both directions."""
    from autodist_tpu.simulator.cost_model import CostModel
    from autodist_tpu.simulator.search import (SearchSpace,
                                               search_strategies)

    cfg = _lm_cfg()
    tr = _lm_trainable(cfg)
    tr.tokens_per_step = 64 * 512
    spec = ResourceSpec(TP_SPEC)
    space = SearchSpace(tp=(2,), num_microbatches=(2,),
                        vocab_parallel=(False,), zero_stage=(0,),
                        comm_overlap=(None,),
                        collective_precision=("int8",),
                        compressor=("none",), seed_zoo=False)
    slow = search_strategies(
        tr, spec, space,
        cost_model=CostModel(spec, link_profile={"ici_gbps": 0.05},
                             quant_profile={"int8_s_per_elem": 1e-12}))
    assert slow.winner is not None and "kern" in slow.winner.name
    fast = search_strategies(
        tr, spec, space,
        cost_model=CostModel(
            spec, link_profile={"ici_gbps": 1e5},
            quant_profile={"int8_s_per_elem": 1e-7},
            kernel_profile={"quant_ring_qdq_factor": 4.0}))
    assert fast.winner is not None and "kern" not in fast.winner.name
    # Both points were enumerated and priced in both runs.
    names = {c.name for c in slow.frontier}
    assert any("kern" in n for n in names) \
        and any("kern" not in n for n in names)


def test_search_matmul_kernel_election_flips_both_directions():
    """Regression: the fused collective-matmul proxy is one-sidedly
    better (a launch credit with no offsetting term), so dominance
    pruning inside one sibling group would delete the composed sibling
    before real pricing — and the election could never flip back to
    composed when calibration disfavors fusion.  Kernel points group
    separately (KnobConfig.mesh_key), so BOTH must reach pricing and
    the winner must follow the calibrated fused_hop_alpha_s."""
    from autodist_tpu.simulator.cost_model import CostModel
    from autodist_tpu.simulator.search import (SearchSpace,
                                               search_strategies)

    cfg = _lm_cfg()
    tr = _lm_trainable(cfg)
    tr.tokens_per_step = 64 * 512
    spec = ResourceSpec(TP_SPEC)
    space = SearchSpace(tp=(2,), num_microbatches=(2,),
                        vocab_parallel=(False,), zero_stage=(0,),
                        comm_overlap=("matmul",),
                        collective_precision=(None,),
                        compressor=("none",), seed_zoo=False)
    fused_wins = search_strategies(
        tr, spec, space,
        cost_model=CostModel(
            spec, link_profile={"hop_alpha_s": 1e-2},
            kernel_profile={"fused_hop_alpha_s": 1e-8}))
    assert fused_wins.winner is not None \
        and "kern" in fused_wins.winner.name
    composed_wins = search_strategies(
        tr, spec, space,
        cost_model=CostModel(
            spec, link_profile={"hop_alpha_s": 1e-8},
            kernel_profile={"fused_hop_alpha_s": 1e-2}))
    assert composed_wins.winner is not None \
        and "kern" not in composed_wins.winner.name, \
        composed_wins.winner.name
    names = {c.name for c in composed_wins.frontier}
    assert any("kern" in n for n in names) \
        and any("kern" not in n for n in names)


def test_flash_decode_election_pinned_both_directions():
    from autodist_tpu.simulator.cost_model import CostModel

    cfg = _lm_cfg(max_len=64)
    tr = _lm_trainable(cfg)
    spec = ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 8}})
    cm = CostModel(spec, kernel_profile={
        "flash_decode_crossover_len": 1024,
        "flash_decode_speedup": 1.6,
        "flash_decode_short_penalty": 0.8})
    flash = {"tensor_parallel": 1, "kernel": ("flash_decode",)}
    einsum = {"tensor_parallel": 1}
    # Past the crossover: flash wins.
    long_f = cm.decode_cost(tr, flash, max_len=4096)
    long_e = cm.decode_cost(tr, einsum, max_len=4096)
    assert long_f.token_time_s < long_e.token_time_s
    assert long_f.kernel == ("flash_decode",)
    # Below it: the kernel's fixed overhead loses to plain einsum.
    short_f = cm.decode_cost(tr, flash, max_len=128)
    short_e = cm.decode_cost(tr, einsum, max_len=128)
    assert short_f.token_time_s > short_e.token_time_s


def test_rank_serving_orders_flash_by_crossover():
    from autodist_tpu.simulator import rank_serving

    cfg = _lm_cfg(max_len=64)
    tr = _lm_trainable(cfg)
    spec = ResourceSpec({"topology": {"platform": "cpu",
                                      "num_devices": 8}})
    cands = [{"tensor_parallel": 1},
             {"tensor_parallel": 1, "kernel": ("flash_decode",)}]
    long = rank_serving(tr, spec, cands, max_len=4096)
    assert long[0][0].get("kernel") == ("flash_decode",)
    short = rank_serving(tr, spec, cands, max_len=128)
    assert short[0][0].get("kernel") is None


# --------------------------------------------------------------------------- #
# Serving engine: the attention_fn gate + flash decode parity
# --------------------------------------------------------------------------- #
def test_engine_rejects_foreign_attention_fn_naming_the_kernel():
    from autodist_tpu.serving import ServingEngine

    cfg = _lm_cfg()
    params = _lm_trainable(cfg).params
    bad = dataclasses.replace(cfg,
                              attention_fn=lambda q, k, v, m, r: q)
    with pytest.raises(NotImplementedError, match="flash"):
        ServingEngine(bad, params, num_slots=2)
    # A non-attention helper that happens to live in ops/
    # flash_attention.py (here: make_attention_fn itself, uncalled) is
    # NOT the flash family — it must get the same coded rejection, not
    # a trace-time shape error inside prefill.
    from autodist_tpu.ops import make_attention_fn
    oops = dataclasses.replace(cfg, attention_fn=make_attention_fn)
    with pytest.raises(NotImplementedError, match="flash"):
        ServingEngine(oops, params, num_slots=2)


def test_engine_flash_decode_greedy_parity_with_attention_fn():
    """The decode-parity gate: with the flash attention_fn accepted,
    greedy decode stays token-for-token against the sequential_logits
    reference (which runs the same attention_fn)."""
    from autodist_tpu.models.pipeline_lm import sequential_logits
    from autodist_tpu.ops import make_attention_fn
    from autodist_tpu.serving import ServingEngine

    base = _lm_cfg(vocab_size=33, max_len=24)
    params = _lm_trainable(base).params
    cfg = dataclasses.replace(base, attention_fn=make_attention_fn(
        causal=True, block_q=8, block_k=8))
    eng = ServingEngine(cfg, params, num_slots=2, max_len=24,
                        prefill_len=8, decode_steps=4)
    assert eng.kernel.get("flash_decode")
    r = np.random.RandomState(1)
    prompts = np.zeros((2, 8), np.int32)
    p_lens = np.array([5, 3], np.int32)
    prompts[0, :5] = r.randint(1, 33, 5)
    prompts[1, :3] = r.randint(1, 33, 3)
    toks = [eng.prefill(prompts, p_lens, np.array([True, True]))]
    for _ in range(2):
        toks.extend(list(eng.decode(np.array([True, True]))))
    gen = np.stack(toks)

    def ref_greedy(prompt, plen, steps):
        seq = list(prompt[:plen])
        out = []
        for _ in range(steps):
            logits = sequential_logits(cfg, params,
                                       jnp.asarray(seq)[None])
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            seq.append(nxt)
        return out

    for b in range(2):
        assert [int(t[b]) for t in gen] == ref_greedy(
            prompts[b], p_lens[b], len(gen))


def test_engine_seeds_kernel_from_strategy():
    from autodist_tpu.serving.engine import seed_engine_kwargs
    from autodist_tpu.strategy.parallel_builders import Pipeline

    cfg = _lm_cfg()
    tr = _lm_trainable(cfg)
    s = Pipeline(num_microbatches=2, tensor_parallel=2,
                 collective_precision={"tp_psum": "int8"},
                 kernel=("quant_ring", "flash_decode")).build(
        tr, ResourceSpec(TP_SPEC))
    kw = seed_engine_kwargs({}, s)
    assert kw["kernel"] == {"flash_decode": True, "quant_ring": True}


# --------------------------------------------------------------------------- #
# Telemetry: the kernel/<name>_elected schema gate
# --------------------------------------------------------------------------- #
def _write_run(tmp_path, gauges, run_annotations):
    import time as _time

    run = tmp_path / "run"
    run.mkdir(parents=True)
    with open(run / "metrics.jsonl", "w") as f:
        for name, value in gauges:
            f.write(json.dumps({"kind": "gauge", "name": name,
                                "value": value}) + "\n")
    with open(run / "manifest.json", "w") as f:
        json.dump({"kind": "manifest", "provenance": {},
                   "time": _time.time(), "run": run_annotations}, f)
    return str(run)


def test_telemetry_check_gates_kernel_gauge(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", "tools/telemetry_report.py")
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)

    ok = _write_run(tmp_path, [("kernel/quant_ring_elected", 1)],
                    {"kernel": ["quant_ring"]})
    assert tr.check_schema(ok) == []
    # Declared but never elected: the gauge is missing.
    missing = _write_run(tmp_path.joinpath("m"),
                         [], {"kernel": ["quant_ring"]})
    assert any("quant_ring" in p for p in tr.check_schema(missing))
    # A gauge naming an unregistered kernel fails.
    bogus = _write_run(tmp_path.joinpath("b"),
                       [("kernel/warp_drive_elected", 1)], {})
    assert any("unregistered" in p for p in tr.check_schema(bogus))


def test_pipeline_lowering_emits_kernel_gauge():
    from autodist_tpu import telemetry

    cfg = _lm_cfg()
    batch = _lm_batch(cfg.vocab_size)
    runner = AutoDist(TP_SPEC, "Pipeline", num_microbatches=2,
                      tensor_parallel=2,
                      collective_precision={"tp_psum": "int8"},
                      kernel=("quant_ring",)).build(_lm_trainable(cfg))
    try:
        gauge = telemetry.get().gauge("kernel/quant_ring_elected")
        assert gauge.value == 1
    finally:
        runner.close()
