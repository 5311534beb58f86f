"""Vocab-parallel embedding/unembedding + the streaming fused
cross-entropy epilogue (``Pipeline(vocab_parallel=True)``).

Correctness is pinned the way the dp×pp×tp composition pinned TP
(``test_pipeline_tp.py``): goldens against the *sequential
single-device* reference — ``PipelineTrainable.loss`` runs the
replicated loss head (``models/losses.py``) on full parameters with
zero collectives — for vocab-parallel × tp ∈ {1, 2} across microbatch
counts, composed with ZeRO-1, bf16_ef, and ``comm_overlap``; plus the
edge cases the sharding introduces (V % tp ≠ 0 zero-pad, padded-row
exclusion from max/sum-exp/argmax) and a primitive-level fwd/bwd parity
test for :func:`vocab_parallel_cross_entropy` under ``shard_map``.

Tolerances mirror the TP goldens: sgd at 1e-5 rtol — vocab parallelism
only re-orders the softmax reduction sums.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from autodist_tpu import AutoDist
from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
from autodist_tpu.models.transformer import TransformerConfig

SPEC_3D = {"topology": {"platform": "cpu", "num_devices": 8},
           "mesh": {"data": 2, "pipe": 2, "model": 2}}
SPEC_2D = {"topology": {"platform": "cpu", "num_devices": 8},
           "mesh": {"data": 4, "pipe": 2}}


def make_cfg(vocab=32):
    return TransformerConfig(vocab_size=vocab, hidden_size=16, num_layers=2,
                             num_heads=2, mlp_dim=32, max_len=8,
                             dtype=jnp.float32, dropout_rate=0.0,
                             attention_dropout_rate=0.0)


def make_lm(opt=None, cfg=None, seed=0):
    return make_pipeline_lm_trainable(cfg or make_cfg(),
                                      opt or optax.sgd(0.05),
                                      jax.random.PRNGKey(seed))


def lm_batches(n, vocab=32, seed=0):
    r = np.random.RandomState(seed)
    return [{"x": r.randint(0, vocab, (8, 8)).astype(np.int32),
             "y": r.randint(0, vocab, (8, 8)).astype(np.int32)}
            for _ in range(n)]


def sequential_train(trainable, batches):
    """Single-device reference: the trainable's own sequential loss."""
    params = trainable.params
    opt_state = trainable.optimizer.init(params)
    losses = []
    for b in batches:
        def loss_for(p):
            l, _, _ = trainable.loss(p, None, jax.tree.map(jnp.asarray, b),
                                     jax.random.PRNGKey(0))
            return l
        losses.append(float(loss_for(params)))
        g = jax.grad(loss_for)(params)
        upd, opt_state = trainable.optimizer.update(g, opt_state, params)
        params = optax.apply_updates(params, upd)
    return jax.device_get(params), losses


def assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol), a, b)


def run_and_compare(runner, trainable_fn, batches, rtol=1e-5, atol=1e-6):
    losses = [float(np.asarray(runner.step(b, rng=jax.random.PRNGKey(0))
                               ["loss"])) for b in batches]
    ref_params, ref_losses = sequential_train(trainable_fn(), batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=rtol, atol=atol)
    assert_trees_close(runner.get_params(), ref_params, rtol=rtol,
                       atol=atol)


# --------------------------------------------------------------------------- #
# Primitive-level fwd/bwd parity
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("vocab", [10, 9])   # divisible and zero-padded
def test_cross_entropy_primitive_fwd_bwd_parity(vocab):
    """vocab_parallel_cross_entropy under a 2-shard shard_map ==
    the replicated models/losses.py math — value, prediction, and
    gradients wrt hidden states AND the (re-assembled) sharded table —
    including the V % tp != 0 zero-pad with padded rows excluded from
    max/sum-exp/argmax."""
    from jax.sharding import Mesh
    from autodist_tpu.kernel.common import pad_axis_to
    from autodist_tpu.models.losses import cross_entropy_from_logits
    from autodist_tpu.parallel.tensor import (vocab_parallel_cross_entropy,
                                              vocab_pad)

    tp, B, L, H = 2, 2, 4, 8
    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(B, L, H), jnp.float32)
    emb = jnp.asarray(r.randn(vocab, H) * 0.5, jnp.float32)
    targets = jnp.asarray(r.randint(0, vocab, (B, L)), jnp.int32)

    # reference: replicated log-softmax on full logits
    def ref_loss(x, emb):
        logits = x @ emb.T
        return jnp.mean(cross_entropy_from_logits(logits, targets))

    ref_val = ref_loss(x, emb)
    ref_dx, ref_demb = jax.grad(ref_loss, argnums=(0, 1))(x, emb)
    ref_pred = np.asarray((x @ emb.T).argmax(-1))

    padded = pad_axis_to(emb, 0, vocab + vocab_pad(vocab, tp))
    mesh = Mesh(np.array(jax.devices()[:tp]), ("model",))

    def local(x, emb_shard):
        def loss(x, e):
            nll, pred = vocab_parallel_cross_entropy(
                x, e, targets, vocab_size=vocab, model_axis="model",
                seq_chunk=2)
            return jnp.mean(nll), pred
        (val, pred), (dx, de) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(x, emb_shard)
        return val, pred, dx, de

    val, pred, dx, de = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P("model", None)),
        out_specs=(P(), P(), P(), P("model", None)),
        check_vma=False)(x, padded)

    np.testing.assert_allclose(float(val), float(ref_val), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(pred), ref_pred)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(ref_dx),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(de)[:vocab],
                               np.asarray(ref_demb), rtol=1e-5, atol=1e-6)
    # zero-padded rows never receive gradient
    np.testing.assert_array_equal(np.asarray(de)[vocab:], 0.0)


def test_vocab_parallel_embedding_exact():
    """The masked shard lookup + psum equals the full-table lookup
    bitwise (one shard contributes the row, the rest zeros)."""
    from jax.sharding import Mesh
    from autodist_tpu.kernel.common import pad_axis_to
    from autodist_tpu.parallel.tensor import (vocab_parallel_embedding,
                                              vocab_pad)

    tp, vocab, H = 2, 7, 4
    r = np.random.RandomState(0)
    emb = jnp.asarray(r.randn(vocab, H), jnp.float32)
    tokens = jnp.asarray(r.randint(0, vocab, (3, 5)), jnp.int32)
    padded = pad_axis_to(emb, 0, vocab + vocab_pad(vocab, tp))
    mesh = Mesh(np.array(jax.devices()[:tp]), ("model",))
    out = jax.shard_map(
        lambda t, e: vocab_parallel_embedding(t, e, model_axis="model"),
        mesh=mesh, in_specs=(P(), P("model", None)), out_specs=P(),
        check_vma=False)(tokens, padded)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(emb[tokens]))


# --------------------------------------------------------------------------- #
# End-to-end goldens vs the sequential reference
# --------------------------------------------------------------------------- #
def test_vocab_parallel_tp2_matches_sequential_reference():
    """The headline golden: dp=2 x pp=2 x tp=2 with the shared embedding
    vocab-sharded reproduces the sequential single-device reference —
    losses AND parameters — with the tied table genuinely stored
    P('model', None) and its optimizer state sharded alongside."""
    runner = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                      tensor_parallel=2, vocab_parallel=True).build(make_lm())
    run_and_compare(runner, make_lm, lm_batches(3))
    emb = runner.state["params"]["shared"]["embedding"]
    # jit round trips may normalize the trailing None away
    assert emb.sharding.spec in (P("model", None), P("model"))
    assert runner.state["params"]["shared"]["pos_embed"].sharding.spec == P()


def test_vocab_parallel_tp1_is_recorded_noop():
    """vocab_parallel=True with tensor_parallel=1 (no model axis): the
    knob is recorded in the strategy but the lowering replicates —
    exact parity with the sequential reference."""
    ad = AutoDist(SPEC_2D, "Pipeline", num_microbatches=2,
                  vocab_parallel=True)
    strategy = ad.build_or_load_strategy(make_lm())
    assert strategy.graph_config.parallel["vocab_parallel"] is True
    runner = ad.build(make_lm(), strategy)
    run_and_compare(runner, make_lm, lm_batches(2))


def test_vocab_parallel_non_divisible_vocab_zero_pads():
    """V=33 over tp=2: storage zero-pads to 34 rows, padded logits are
    excluded from max/sum-exp, get_params returns the unpadded [33, H]
    table, and the run reproduces the sequential reference."""
    cfg = make_cfg(vocab=33)
    runner = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                      tensor_parallel=2, vocab_parallel=True).build(
                          make_lm(cfg=cfg))
    assert runner.state["params"]["shared"]["embedding"].shape == (34, 16)
    run_and_compare(runner, lambda: make_lm(cfg=cfg),
                    lm_batches(3, vocab=33))
    assert runner.get_params()["shared"]["embedding"].shape == (33, 16)


@pytest.mark.slow
@pytest.mark.parametrize("num_microbatches", [1, 4])
def test_vocab_parallel_microbatch_counts_match(num_microbatches):
    runner = AutoDist(SPEC_3D, "Pipeline",
                      num_microbatches=num_microbatches,
                      tensor_parallel=2, vocab_parallel=True).build(make_lm())
    run_and_compare(runner, make_lm, lm_batches(2))


def test_vocab_parallel_comm_overlap_matches():
    """The epilogue psums lower through the PR 2 rs+ag machinery: same
    math, different summation order — goldens hold at the sgd
    tolerance for both decompositions."""
    for mode in ("rsag", "matmul"):
        runner = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                          tensor_parallel=2, vocab_parallel=True,
                          comm_overlap=mode).build(make_lm())
        run_and_compare(runner, make_lm, lm_batches(2))
        runner.close()


def test_vocab_parallel_zero1_shards_embedding_state_and_matches():
    """ZeRO composes with the vocab-sharded table *properly* (the
    ROADMAP carry-over): instead of warn-and-degrade, the model-sharded
    embedding's optimizer state shards ADDITIONALLY over pipe x data —
    flat moments at 1/(tp·pipe·data), update space
    P(('model','pipe','data')) — while model-replicated shared vars keep
    their flat (pipe x data) moments, and numerics match the plain run."""
    r0 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, vocab_parallel=True).build(make_lm())
    r1 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, vocab_parallel=True,
                  zero1=True).build(make_lm())
    for b in lm_batches(2):
        r0.step(b, rng=jax.random.PRNGKey(0))
        r1.step(b, rng=jax.random.PRNGKey(0))
    assert_trees_close(r1.get_params(), r0.get_params())

    ra = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, vocab_parallel=True,
                  zero1=True).build(make_lm(optax.adam(1e-2)))
    ra.step(lm_batches(1)[0], rng=jax.random.PRNGKey(0))
    mu = ra.state["opt_state"][0].mu
    emb = mu["shared"]["embedding"]
    assert emb.ndim == 1
    assert emb.sharding.spec == P(("model", "pipe", "data")), \
        emb.sharding.spec
    # the parameter itself keeps its model-axis storage (state-only
    # extra sharding; the stored table is still [V_pad/tp, H] per shard)
    assert ra.state["params"]["shared"]["embedding"].sharding.spec \
        in (P("model"), P("model", None))
    ln = mu["shared"]["ln_final_scale"]
    assert ln.ndim == 1 and ln.sharding.spec == P(("pipe", "data"))
    # nothing degraded silently: the plan records no fallback for the
    # table (tp-sharded stage vars do degrade, with reasons recorded)
    deg = ra.lowered.zero_degraded
    assert "shared/embedding" not in deg
    assert any(k.startswith("stages/") for k in deg)


def test_vocab_parallel_zero3_degrades_to_state_sharding_with_record():
    """zero_stage=3 on the model-sharded table: the parameter is already
    1/tp-sharded, so stage 3 degrades to the state-sharding form — and
    the lowered plan records the reason (no log-warning contract)."""
    r0 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, vocab_parallel=True).build(make_lm())
    r3 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, vocab_parallel=True,
                  zero_stage=3).build(make_lm())
    for b in lm_batches(2):
        r0.step(b, rng=jax.random.PRNGKey(0))
        r3.step(b, rng=jax.random.PRNGKey(0))
    assert_trees_close(r3.get_params(), r0.get_params())
    assert "shared/embedding" in r3.lowered.zero_degraded
    # model-replicated shared vars DO store stage-3 sharded
    ln = r3.state["params"]["shared"]["ln_final_scale"]
    assert ln.ndim == 1 and ln.sharding.spec == P(("pipe", "data"))


@pytest.mark.slow
def test_vocab_parallel_bf16_ef_compressor_composes():
    """bf16_ef over the data axis composes with the vocab-sharded
    embedding (its grad psums over pipe at full precision first, EF
    residual rows sized from the model-local shard)."""
    r0 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, vocab_parallel=True).build(make_lm())
    r1 = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, vocab_parallel=True,
                  compressor="bf16_ef").build(make_lm())
    for b in lm_batches(2):
        r0.step(b, rng=jax.random.PRNGKey(0))
        r1.step(b, rng=jax.random.PRNGKey(0))
    assert_trees_close(r1.get_params(), r0.get_params(), rtol=5e-2,
                       atol=5e-3)
    # embedding 32x16 = 512 over model(2) shards -> 256-length local
    # residual rows, one per device
    assert r1.state["sync_state"]["shared/embedding"].shape == (8, 256)


# --------------------------------------------------------------------------- #
# Strategy IR, validation, cost model
# --------------------------------------------------------------------------- #
def test_vocab_strategy_ir_round_trip_and_validation():
    from autodist_tpu.strategy.ir import Strategy
    from autodist_tpu.strategy.parallel_builders import Pipeline
    from autodist_tpu.resource import ResourceSpec

    ad = AutoDist(SPEC_3D, "Pipeline", num_microbatches=2,
                  tensor_parallel=2, vocab_parallel=True)
    strategy = ad.build_or_load_strategy(make_lm())
    assert strategy.graph_config.parallel["vocab_parallel"] is True
    clone = Strategy.from_json(strategy.to_json())
    by_name = {n.var_name: n for n in clone.node_configs}
    assert by_name["shared/embedding"].partitioner.spec == ["model", None]
    assert by_name["shared/pos_embed"].partitioner is None

    rs3 = ResourceSpec(SPEC_3D)
    # a trainable with no shared params cannot vocab-shard
    from autodist_tpu import PipelineTrainable
    stacked = {"wi": {"kernel": jnp.zeros((2, 8, 16))},
               "wo": {"kernel": jnp.zeros((2, 16, 8))}}
    mlp = PipelineTrainable(
        lambda p, x, model_axis=None: x, stacked,
        lambda o, b: (jnp.mean(o), {}), optax.sgd(0.1), num_stages=2)
    with pytest.raises(ValueError, match="shared"):
        Pipeline(num_microbatches=2, tensor_parallel=2,
                 vocab_parallel=True).build(mlp, rs3)

    # a loss head that is not vocab-parallel aware is rejected at build
    # time (so AutoStrategy's candidate loop skips, not crashes)
    lm = make_lm()
    lm.loss_head = lambda outputs, batch, shared: (jnp.mean(outputs), {})
    with pytest.raises(ValueError, match="model_axis"):
        Pipeline(num_microbatches=2, tensor_parallel=2,
                 vocab_parallel=True).build(lm, rs3)

    # ... and with comm_overlap set, the head must accept that too —
    # at build time, so AutoStrategy skips instead of failing at compile
    lm2 = make_lm()
    lm2.loss_head = lambda outputs, batch, shared, model_axis=None: (
        jnp.mean(outputs), {})
    with pytest.raises(ValueError, match="comm_overlap"):
        Pipeline(num_microbatches=2, tensor_parallel=2,
                 vocab_parallel=True, comm_overlap="rsag").build(lm2, rs3)


def test_cost_model_vocab_parallel_divides_memory_terms():
    """Acceptance: embedding optimizer state and peak logits memory
    reduced by 1/tp under vocab_parallel=True, and the candidate
    ranking sees it."""
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator.cost_model import CostModel
    from autodist_tpu.strategy.parallel_builders import Pipeline

    t0, t1 = make_lm(), make_lm()
    for t in (t0, t1):
        t.tokens_per_step = 4096
        t.act_bytes_per_token = 64.0
    rs = ResourceSpec(SPEC_3D)
    cm = CostModel(rs)
    s0 = Pipeline(num_microbatches=2, tensor_parallel=2).build(t0, rs)
    s1 = Pipeline(num_microbatches=2, tensor_parallel=2,
                  vocab_parallel=True).build(t1, rs)
    c0 = cm.strategy_cost(t0, s0)
    c1 = cm.strategy_cost(t1, s1)
    # peak logits exactly /tp ...
    assert c1.peak_logits_bytes == pytest.approx(c0.peak_logits_bytes / 2)
    assert c1.peak_logits_bytes > 0
    # ... and total per-device memory strictly shrinks (embedding
    # params + moments + logits all divided)
    assert c1.mem_bytes_per_device < c0.mem_bytes_per_device
    V, H = 32, 16
    emb_bytes = V * H * 4.0
    expected_drop = (emb_bytes * (2.0 + cm.opt_state_multiplier) / 2
                     + c0.peak_logits_bytes / 2)
    assert (c0.mem_bytes_per_device - c1.mem_bytes_per_device) \
        == pytest.approx(expected_drop)
    # the epilogue's psums are priced: more collectives, more bytes
    assert c1.num_collectives > c0.num_collectives


def test_auto_strategy_zoo_ranks_vocab_parallel_candidate():
    """The AutoStrategy zoo scores the vocab-parallel candidate on a 3D
    mesh, and its memory column reflects the 1/tp shrink vs the
    blocking tp=2 candidate."""
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator.auto_strategy import AutoStrategy

    lm = make_lm()
    lm.tokens_per_step = 4096
    lm.act_bytes_per_token = 64.0
    auto = AutoStrategy()
    auto.build(lm, ResourceSpec(SPEC_3D))
    # candidate names are positional (#k suffixes), so identify the
    # vocab-parallel candidate by its unique memory signature: the
    # pipeline candidate whose peak-logits term halved.
    logits_terms = sorted({c.peak_logits_bytes for _, c in auto.report
                           if c.peak_logits_bytes > 0})
    assert len(logits_terms) >= 2, "no vocab-parallel candidate scored"
    assert logits_terms[0] == pytest.approx(logits_terms[-1] / 2)
