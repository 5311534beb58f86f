"""Latency-hiding collectives for the tensor-parallel path.

The collective-matmul decomposition (``comm_overlap``): the row-parallel
output all-reduce splits into a reduce-scatter/all-gather pair
(``"rsag"``) or a chunked ``ppermute`` ring whose per-hop transfer
overlaps per-chunk compute (``"matmul"``).  Correctness is pinned the
way the dp×pp×tp composition was (``test_pipeline_tp.py``): goldens
against the blocking ``psum`` path and the sequential single-device
reference for tp ∈ {1, 2}, composed with ZeRO-1, bf16_ef, and virtual
stages — the decomposition may reorder float summation but must change
nothing else.  The HLO-structural half of the claim (zero monolithic
model-axis all-reduce, the ring's collective-permutes) lives in
``test_hlo_probe.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu import AutoDist
from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
from autodist_tpu.models.transformer import TransformerConfig
from autodist_tpu.parallel.tensor import (collective_matmul_row,
                                          column_parallel,
                                          normalize_comm_overlap,
                                          psum_decomposed, row_parallel)

CFG = TransformerConfig(vocab_size=32, hidden_size=16, num_layers=2,
                        num_heads=2, mlp_dim=32, max_len=8,
                        dtype=jnp.float32, dropout_rate=0.0,
                        attention_dropout_rate=0.0)
SPEC_3D = {"topology": {"platform": "cpu", "num_devices": 8},
           "mesh": {"data": 2, "pipe": 2, "model": 2}}


def make_lm(opt=None, cfg=CFG, seed=0):
    return make_pipeline_lm_trainable(cfg, opt or optax.sgd(0.05),
                                      jax.random.PRNGKey(seed))


def lm_batches(n, seed=0):
    r = np.random.RandomState(seed)
    return [{"x": r.randint(0, CFG.vocab_size, (8, 8)).astype(np.int32),
             "y": r.randint(0, CFG.vocab_size, (8, 8)).astype(np.int32)}
            for _ in range(n)]


def train(runner, batches):
    losses = [float(np.asarray(runner.step(b, rng=jax.random.PRNGKey(0))
                               ["loss"])) for b in batches]
    return losses, runner.get_params()


def assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol), a, b)


# --------------------------------------------------------------------------- #
# Primitive-level goldens (pure shard_map, no pipeline)
# --------------------------------------------------------------------------- #
def _model_mesh(tp):
    return Mesh(np.array(jax.devices()[:tp]), ("model",))


@pytest.mark.parametrize("mode", ["rsag", "matmul"])
@pytest.mark.parametrize("tp,width", [(2, 10), (4, 10), (4, 12)])
def test_row_parallel_decomposed_matches_psum(mode, tp, width):
    """Forward AND both gradients of the decomposed row-parallel matmul
    match the blocking psum path — including output widths that don't
    divide the tp degree (the ring's zero-pad path)."""
    mesh = _model_mesh(tp)
    r = np.random.RandomState(0)
    x = r.randn(6, 8).astype(np.float32)
    k = r.randn(8, width).astype(np.float32)

    def run(fn, out_specs=P()):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
            out_specs=out_specs, check_vma=False))

    def value(xs, ks, overlap):
        return row_parallel(xs, ks, model_axis="model",
                            comm_overlap=overlap)

    y_ref = run(lambda a, b: value(a, b, None))(x, k)
    y_dec = run(lambda a, b: value(a, b, mode))(x, k)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)

    def grads(overlap):
        def loss(a, b):
            return jnp.sum(value(a, b, overlap) ** 2)
        return run(lambda a, b: jax.grad(loss, argnums=(0, 1))(a, b),
                   out_specs=(P(None, "model"), P("model", None)))(x, k)

    gx_ref, gk_ref = grads(None)
    gx, gk = grads(mode)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gk_ref),
                               rtol=1e-5, atol=1e-5)


def test_collective_matmul_row_axes2_and_column_backward():
    """The axes=2 contraction (attention out-proj shape) rides the ring,
    and column_parallel's decomposed backward cotangent reduction is
    exact."""
    mesh = _model_mesh(2)
    r = np.random.RandomState(1)
    x = r.randn(3, 4, 5).astype(np.float32)     # [B, heads, head_dim]
    k = r.randn(4, 5, 7).astype(np.float32)     # [heads, head_dim, H]

    def rowf(xs, ks):
        return collective_matmul_row(xs, ks, "model", 2)

    y = jax.jit(jax.shard_map(
        rowf, mesh=mesh, in_specs=(P(None, "model"), P("model",)),
        out_specs=P(), check_vma=False))(x, k)
    np.testing.assert_allclose(np.asarray(y), np.tensordot(x, k, axes=2),
                               rtol=1e-5, atol=1e-6)

    xc = r.randn(6, 8).astype(np.float32)
    kc = r.randn(8, 10).astype(np.float32)

    def col_grads(overlap):
        def loss(a, b):
            return jnp.sum(column_parallel(a, b, model_axis="model",
                                           comm_overlap=overlap) ** 2)
        return jax.jit(jax.shard_map(
            lambda a, b: jax.grad(loss, argnums=(0, 1))(a, b), mesh=mesh,
            in_specs=(P(), P(None, "model")),
            out_specs=(P(), P(None, "model")), check_vma=False))(xc, kc)

    gx_ref, gk_ref = col_grads(None)
    gx, gk = col_grads("rsag")
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gk_ref),
                               rtol=1e-5, atol=1e-5)


def test_psum_decomposed_matches_psum_and_stays_split():
    """psum_decomposed == psum numerically for a non-divisible payload,
    and its compiled HLO carries the reduce-scatter/all-gather pair with
    ZERO all-reduce — the optimization_barrier holds the re-fusion off
    (a reintroduced fused all-reduce fails here, in tier-1, on CPU)."""
    from tools.hlo_probe import collective_counts

    mesh = _model_mesh(4)
    x = np.arange(10, dtype=np.float32)

    def f(v):
        return psum_decomposed(v, "model")

    jitted = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False))
    np.testing.assert_allclose(np.asarray(jitted(x)), x * 4, rtol=1e-6)
    counts = collective_counts(jitted.lower(x).compile().as_text())
    assert counts["all-reduce"] == 0, counts
    assert counts["reduce-scatter"] == 1 and counts["all-gather"] == 1, counts


def test_normalize_comm_overlap():
    assert normalize_comm_overlap(None) is None
    assert normalize_comm_overlap(False) is None
    assert normalize_comm_overlap("") is None
    assert normalize_comm_overlap(True) == "matmul"
    assert normalize_comm_overlap("rsag") == "rsag"
    with pytest.raises(ValueError, match="comm_overlap"):
        normalize_comm_overlap("bogus")


# --------------------------------------------------------------------------- #
# End-to-end goldens: overlapped pipeline == blocking pipeline == sequential
# --------------------------------------------------------------------------- #
def test_tp2_overlap_matches_blocking_and_sequential():
    """The headline golden: dp=2 × pp=2 × tp=2 training with BOTH
    decompositions reproduces the blocking-psum run and the sequential
    single-device reference — losses and parameters."""
    from tests.unit.test_pipeline_tp import sequential_train

    blk_l, blk_p = train(
        AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                 tensor_parallel=2).build(make_lm()), lm_batches(3))
    ref_p, ref_l = sequential_train(make_lm(), lm_batches(3))
    for mode in ("rsag", "matmul"):
        losses, params = train(
            AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                     tensor_parallel=2, comm_overlap=mode).build(make_lm()),
            lm_batches(3))
        np.testing.assert_allclose(losses, blk_l, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(losses, ref_l, rtol=1e-5, atol=1e-6)
        assert_trees_close(params, blk_p)
        assert_trees_close(params, ref_p)


@pytest.mark.slow
def test_tp1_overlap_is_a_noop():
    """tp=1 with the knob set: the builder records it, the lowering runs
    zero collectives either way, parity with the sequential reference is
    exact — completing the tp ∈ {1, 2} golden matrix."""
    from tests.unit.test_pipeline_tp import sequential_train

    spec = {"topology": {"platform": "cpu", "num_devices": 8},
            "mesh": {"data": 4, "pipe": 2}}
    runner = AutoDist(spec, "Pipeline", num_microbatches=2,
                      comm_overlap="matmul").build(make_lm())
    losses, params = train(runner, lm_batches(2))
    ref_p, ref_l = sequential_train(make_lm(), lm_batches(2))
    np.testing.assert_allclose(losses, ref_l, rtol=1e-5, atol=1e-6)
    assert_trees_close(params, ref_p)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["rsag", "matmul"])
def test_tp2_overlap_composes_with_zero1(mode):
    r0 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, zero1=True).build(make_lm())
    r1 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, zero1=True,
                  comm_overlap=mode).build(make_lm())
    l0, p0 = train(r0, lm_batches(2))
    l1, p1 = train(r1, lm_batches(2))
    np.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-6)
    assert_trees_close(p1, p0)


@pytest.mark.slow
def test_tp2_overlap_composes_with_bf16_ef():
    r0 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, compressor="bf16_ef").build(make_lm())
    r1 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, compressor="bf16_ef",
                  comm_overlap="matmul").build(make_lm())
    l0, p0 = train(r0, lm_batches(2))
    l1, p1 = train(r1, lm_batches(2))
    # bf16 wire quantization amplifies the summation-order difference;
    # the runs must stay close, not bitwise-equal.
    np.testing.assert_allclose(l1, l0, rtol=1e-4, atol=1e-4)
    assert_trees_close(p1, p0, rtol=5e-3, atol=5e-4)


@pytest.mark.slow
def test_tp2_overlap_composes_with_virtual_stages():
    """Megatron interleaving (V=2, 4 logical stages) under the chunked
    collective matmul — the ring-in-a-ring composition."""
    cfg4 = TransformerConfig(vocab_size=32, hidden_size=16, num_layers=4,
                             num_heads=2, mlp_dim=32, max_len=8,
                             dtype=jnp.float32, dropout_rate=0.0,
                             attention_dropout_rate=0.0)
    r0 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=4,
                  virtual_stages=2, tensor_parallel=2).build(
                      make_lm(cfg=cfg4, seed=1))
    r1 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=4,
                  virtual_stages=2, tensor_parallel=2,
                  comm_overlap="matmul").build(make_lm(cfg=cfg4, seed=1))
    l0, p0 = train(r0, lm_batches(2))
    l1, p1 = train(r1, lm_batches(2))
    np.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-6)
    assert_trees_close(p1, p0)


# --------------------------------------------------------------------------- #
# Strategy IR + lowering contracts
# --------------------------------------------------------------------------- #
def test_comm_overlap_ir_round_trip_and_validation():
    """The comm_overlap field survives serialization per variable and in
    the graph knob (chief→worker handoff); True canonicalizes to
    'matmul'; a non-overlap-aware stage_fn is rejected loudly."""
    from autodist_tpu.strategy.ir import Strategy

    ad = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, comm_overlap=True)
    strategy = ad.build_or_load_strategy(make_lm())
    assert strategy.graph_config.parallel["comm_overlap"] == "matmul"
    clone = Strategy.from_json(strategy.to_json())
    by_name = {n.var_name: n for n in clone.node_configs}
    # tp-sharded vars carry the mode; model-replicated ones don't.
    assert by_name["stages/mlp/wo/kernel"].partitioner.comm_overlap == \
        "matmul"
    assert by_name["stages/attention/qkv/kernel"].partitioner.comm_overlap \
        == "matmul"
    assert by_name["stages/ln_mlp/scale"].partitioner.comm_overlap is None

    # a stage_fn without the comm_overlap keyword cannot honor the knob
    from autodist_tpu import PipelineTrainable
    stacked = {"wi": {"kernel": jnp.zeros((2, 8, 16))},
               "wo": {"kernel": jnp.zeros((2, 16, 8))}}
    mlp = PipelineTrainable(
        lambda p, x, model_axis=None: x, stacked,
        lambda o, b: (jnp.mean(o), {}), optax.sgd(0.1), num_stages=2)
    with pytest.raises(ValueError, match="comm_overlap"):
        AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                 tensor_parallel=2, comm_overlap="rsag").build(mlp)


def test_hand_edited_per_variable_overlap_drives_lowering():
    """A strategy whose graph knob is unset but whose tp-sharded node
    configs carry comm_overlap still lowers decomposed (the per-layer
    selectability the IR field exists for); disagreeing modes are
    rejected."""
    ad = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2)
    strategy = ad.build_or_load_strategy(make_lm())
    strategy.graph_config.parallel["comm_overlap"] = None
    tp_nodes = [n for n in strategy.node_configs
                if n.partitioner is not None and n.partitioner.spec
                and "model" in n.partitioner.spec[1:]]
    assert tp_nodes
    for n in tp_nodes:
        n.partitioner.comm_overlap = "rsag"
    runner = AutoDist(SPEC_3D).build(make_lm(), strategy)
    losses, _ = train(runner, lm_batches(1))
    assert np.isfinite(losses).all()

    tp_nodes[0].partitioner.comm_overlap = "matmul"
    with pytest.raises(ValueError, match="disagree"):
        AutoDist(SPEC_3D).build(make_lm(), strategy)


# --------------------------------------------------------------------------- #
# Overlap-aware cost model
# --------------------------------------------------------------------------- #
def _hinted_lm():
    t = make_lm()
    t.tokens_per_step = 4096
    t.act_bytes_per_token = 64.0
    return t


@pytest.mark.parametrize("profile", [
    None,
    {"ici_gbps": 1.0},                    # starved link: comm-bound
    {"ici_gbps": 400.0},                  # fat link
    {"hop_alpha_s": 1e-4},                # latency-dominated
    {"hop_alpha_s": 1e-7, "ici_gbps": 10.0},
    {"mxu_efficiency": 0.05},             # slow compute hides more comm
])
def test_cost_model_ranks_overlap_at_or_below_blocking(profile):
    """For EVERY calibrated link profile the overlapped variant prices
    ≤ the blocking one (the lowering can always fall back to the fused
    all-reduce, so the model caps at the blocking envelope), with the
    same wire bytes reported and a feasible-memory story unchanged."""
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator.cost_model import CostModel
    from autodist_tpu.strategy.parallel_builders import Pipeline

    rs = ResourceSpec(SPEC_3D)
    cm = CostModel(rs, link_profile=profile)
    t = _hinted_lm()
    blk = cm.strategy_cost(
        t, Pipeline(num_microbatches=2, tensor_parallel=2).build(t, rs))
    for mode in ("rsag", "matmul"):
        ov = cm.strategy_cost(
            t, Pipeline(num_microbatches=2, tensor_parallel=2,
                        comm_overlap=mode).build(t, rs))
        assert ov.comm_time_s <= blk.comm_time_s * (1 + 1e-12)
        assert ov.score <= blk.score * (1 + 1e-12)
        # same wire volume — the decomposition moves bytes differently,
        # it does not remove them
        assert ov.comm_bytes == pytest.approx(blk.comm_bytes)
        assert ov.mem_bytes_per_device == pytest.approx(
            blk.mem_bytes_per_device)


def test_cost_model_overlap_wins_when_compute_hides_hops():
    """On a link profile where chunk compute genuinely covers hop
    latency the overlapped plan is STRICTLY cheaper — the lever
    AutoStrategy's comm_overlap candidate exists to exploit."""
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator.cost_model import CostModel
    from autodist_tpu.strategy.parallel_builders import Pipeline

    rs = ResourceSpec(SPEC_3D)
    cm = CostModel(rs, link_profile={"hop_alpha_s": 1e-7,
                                     "ici_gbps": 10.0,
                                     "mxu_efficiency": 0.01})
    t = _hinted_lm()
    blk = cm.strategy_cost(
        t, Pipeline(num_microbatches=2, tensor_parallel=2).build(t, rs))
    ov = cm.strategy_cost(
        t, Pipeline(num_microbatches=2, tensor_parallel=2,
                    comm_overlap="matmul").build(t, rs))
    assert ov.comm_time_s < blk.comm_time_s


def test_calibration_link_section_reaches_cost_model(tmp_path):
    """A measured 'link' section in calibration.json lands in
    LINK_PROFILE and the CostModel picks it up (per-instance overrides
    still win)."""
    import json

    from autodist_tpu.simulator import cost_model as cm

    path = tmp_path / "measured.json"
    path.write_text(json.dumps(
        {"meta": {"backend": "v5e"},
         "compressor_factor": {},
         "link": {"ici_gbps": 123.0, "hop_alpha_s": 2e-6}}))
    saved = dict(cm.LINK_PROFILE)
    try:
        cm.load_calibration(str(path))
        assert cm.LINK_PROFILE["ici_gbps"] == 123.0
        from autodist_tpu.resource import ResourceSpec
        model = cm.CostModel(ResourceSpec(SPEC_3D))
        assert model.link_profile["ici_gbps"] == 123.0
        override = cm.CostModel(ResourceSpec(SPEC_3D),
                                link_profile={"ici_gbps": 7.0})
        assert override.link_profile["ici_gbps"] == 7.0
        assert override.link_profile["hop_alpha_s"] == 2e-6
    finally:
        cm.LINK_PROFILE.clear()
        cm.LINK_PROFILE.update(saved)


def test_latency_hiding_flags_knob(monkeypatch):
    """The runner knob: off by default; skipped on non-TPU targets;
    applied into LIBTPU_INIT_ARGS (libtpu's flag channel — jaxlib's
    XLA_FLAGS parser aborts on TPU flags) for TPU targets; a
    '--'-prefixed value replaces the default list (the escape hatch for
    libtpu flag drift)."""
    from autodist_tpu.kernel import lowering as kl

    env = {}
    monkeypatch.delenv("AUTODIST_TPU_ASYNC_COLLECTIVES", raising=False)
    assert kl.apply_latency_hiding_flags(env, platform="tpu") is False

    monkeypatch.setenv("AUTODIST_TPU_ASYNC_COLLECTIVES", "1")
    assert kl.apply_latency_hiding_flags(env, platform="cpu") is False
    assert "LIBTPU_INIT_ARGS" not in env

    assert kl.apply_latency_hiding_flags(env, platform="tpu") is True
    for flag in kl.LATENCY_HIDING_XLA_FLAGS:
        assert flag in env["LIBTPU_INIT_ARGS"]
    # idempotent
    before = env["LIBTPU_INIT_ARGS"]
    assert kl.apply_latency_hiding_flags(env, platform="tpu") is True
    assert env["LIBTPU_INIT_ARGS"] == before

    monkeypatch.setenv("AUTODIST_TPU_ASYNC_COLLECTIVES",
                       "--xla_custom_flag=true")
    custom = {}
    assert kl.apply_latency_hiding_flags(custom, platform="tpu") is True
    assert custom["LIBTPU_INIT_ARGS"] == "--xla_custom_flag=true"

    monkeypatch.setenv("AUTODIST_TPU_ASYNC_COLLECTIVES", "0")
    assert kl.apply_latency_hiding_flags({}, platform="tpu") is False

    # platform=auto honors the JAX_PLATFORMS pin over libtpu detection
    monkeypatch.setenv("AUTODIST_TPU_ASYNC_COLLECTIVES", "1")
    assert kl.apply_latency_hiding_flags(
        {"JAX_PLATFORMS": "cpu"}, platform="auto") is False


def test_auto_strategy_candidates_include_comm_overlap():
    from autodist_tpu.simulator.auto_strategy import default_candidates
    from autodist_tpu.strategy.parallel_builders import Pipeline

    overlapped = [b for b in default_candidates()
                  if isinstance(b, Pipeline) and b.comm_overlap]
    assert overlapped and overlapped[0].comm_overlap == "matmul"
    assert overlapped[0].tensor_parallel == 2
