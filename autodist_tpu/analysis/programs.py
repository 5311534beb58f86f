"""The compiled-program corpus the program linter sweeps.

Small, CPU-lowerable programs covering every lowering family the repo
ships — the tiny data-parallel trainable, the dp×pp×tp pipeline (plain,
overlapped, vocab-parallel, quantized), the ZeRO-ladder pipeline with a
distinctive non-tp parameter dim, and the serving engine's fused decode
window.  Each text is memoized per process: an 8-device compile costs
tens of seconds, and one compiled text serves ``tools/hlo_probe.py``'s
probes, the program-lint rules, the mutation harness, and the tier-1
tests alike.

Geometry constants are chosen *distinctive* (a vocab of 93, a mix dim
of 29, a cache length of 57 — extents no other tensor dimension
equals), so a shape-scan hit in the facts layer IS the buffer the rule
forbids.
"""
from __future__ import annotations

import functools

from autodist_tpu.analysis.facts import compiled_text


def tiny_trainable():
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import Trainable

    params = {"w": jnp.zeros((16, 4), jnp.float32)}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    return Trainable.from_loss_fn(loss_fn, params, optax.sgd(0.1))


def tiny_batch(n: int = 1):
    import numpy as np

    r = np.random.RandomState(0)
    return {"x": r.randn(8, 16).astype(np.float32),
            "y": r.randn(8, 4).astype(np.float32)}


@functools.lru_cache(maxsize=None)
def tiny_step_text(num_devices: int = 2) -> str:
    """One data-parallel train step of the tiny trainable on an
    ``num_devices``-device mesh (the single-replica bypass program at
    ``num_devices=1``)."""
    import jax

    from autodist_tpu import AllReduce, AutoDist

    spec = {"topology": {"platform": "cpu", "num_devices": num_devices}}
    runner = AutoDist(spec, AllReduce()).build(tiny_trainable())
    try:
        return compiled_text(runner.lowered.step_fn, runner.state,
                             runner._place_batch(tiny_batch()),
                             jax.random.PRNGKey(0))
    finally:
        runner.close()


@functools.lru_cache(maxsize=None)
def tiny_scan_texts(k: int = 4) -> tuple[str, str]:
    """``(text_k, text_1)``: the k-step fused ``run_steps`` program and
    the single-step program it must match collective-for-collective."""
    import jax
    from jax import lax

    from autodist_tpu import AllReduce, AutoDist, stack_steps

    spec = {"topology": {"platform": "cpu", "num_devices": 2}}
    runner = AutoDist(spec, AllReduce()).build(tiny_trainable())
    try:
        step_fn = runner.lowered.step_fn

        def scanned(state, batches, rngs):
            def body(s, xs):
                b, r = xs
                return step_fn(s, b, r)
            return lax.scan(body, state, (batches, rngs))

        stacked = runner.place_steps(stack_steps(
            [tiny_batch() for _ in range(k)]))
        rngs = jax.random.split(jax.random.PRNGKey(0), k)
        text_k = compiled_text(jax.jit(scanned), runner.state, stacked,
                               rngs)
        text_1 = compiled_text(step_fn, runner.state,
                               runner._place_batch(tiny_batch()),
                               jax.random.PRNGKey(0))
    finally:
        runner.close()
    return text_k, text_1


# --------------------------------------------------------------------------- #
# dp×pp×tp pipeline LM programs
# --------------------------------------------------------------------------- #
def pipeline_runner(tensor_parallel: int, comm_overlap=None,
                    vocab_parallel: bool = False, vocab_size: int = 32,
                    collective_precision=None, kernel=None):
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
    from autodist_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=vocab_size, hidden_size=16,
                            num_layers=2,
                            num_heads=2, mlp_dim=32, max_len=8,
                            dtype=jnp.float32, dropout_rate=0.0,
                            attention_dropout_rate=0.0)
    mesh = {"data": 2, "pipe": 2, "model": 2} if tensor_parallel > 1 \
        else {"data": 4, "pipe": 2}
    spec = {"topology": {"platform": "cpu", "num_devices": 8},
            "mesh": mesh}
    trainable = make_pipeline_lm_trainable(cfg, optax.sgd(0.05),
                                           jax.random.PRNGKey(0))
    # Hashable policy form (lru_cache): a ("slot", "prec") tuple-of-
    # pairs stands in for the per-boundary dict.
    if isinstance(collective_precision, tuple):
        collective_precision = dict(collective_precision)
    return AutoDist(spec, "Pipeline", num_microbatches=2,
                    tensor_parallel=tensor_parallel,
                    comm_overlap=comm_overlap,
                    vocab_parallel=vocab_parallel,
                    collective_precision=collective_precision,
                    kernel=kernel).build(trainable)


@functools.lru_cache(maxsize=None)
def pipeline_step_text(tensor_parallel: int, comm_overlap=None,
                       vocab_parallel: bool = False,
                       vocab_size: int = 32,
                       collective_precision=None, kernel=None) -> str:
    """Optimized HLO of one pipeline train step (memoized: the tp=1 and
    blocking tp=2 programs serve several probes/rules — each 8-device
    compile costs tens of seconds, and the bench embeds an all-probes
    run under a budget)."""
    import jax
    import numpy as np

    r = np.random.RandomState(0)
    batch = {"x": r.randint(0, vocab_size, (8, 8)).astype(np.int32),
             "y": r.randint(0, vocab_size, (8, 8)).astype(np.int32)}
    runner = pipeline_runner(tensor_parallel, comm_overlap,
                             vocab_parallel, vocab_size,
                             collective_precision, kernel)
    try:
        return compiled_text(runner.lowered.step_fn, runner.state,
                             runner._place_batch(batch),
                             jax.random.PRNGKey(0))
    finally:
        runner.close()


# --------------------------------------------------------------------------- #
# ZeRO-ladder pipeline programs
# --------------------------------------------------------------------------- #
# Distinctive dim of the probe's non-tp stage matrices: no activation,
# batch, or other parameter carries it, so a hit in the ENTRY signature
# IS a full parameter living across the step boundary.
Z3_DIM = 29
Z3_V = 2          # virtual stages = per-device layers
Z3_LEAVES = 3     # ZeRO-3 stage leaves: mix_in, mix_out, wo/bias


def zero_runner(zero_stage: int, collective_precision=None):
    """dp×pp×tp pipeline (mesh {data:2, pipe:2, model:2}, V=2) whose
    stage has Megatron wi/wo (tp-sharded; their ZeRO requests degrade,
    state shards with the parameter) plus a non-tp ``mix`` pair carrying
    the distinctive :data:`Z3_DIM` — the variables the ZeRO stage
    actually moves."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import AutoDist, PipelineTrainable
    from autodist_tpu.parallel.tensor import column_parallel, row_parallel

    HID, FF, C = 8, 16, 4
    r = np.random.RandomState(0)
    stacked = {
        "wi": {"kernel": jnp.asarray(r.randn(C, HID, FF) * 0.3,
                                     jnp.float32),
               "bias": jnp.zeros((C, FF), jnp.float32)},
        "wo": {"kernel": jnp.asarray(r.randn(C, FF, HID) * 0.3,
                                     jnp.float32),
               "bias": jnp.zeros((C, HID), jnp.float32)},
        "mix_in": jnp.asarray(r.randn(C, HID, Z3_DIM) * 0.3, jnp.float32),
        "mix_out": jnp.asarray(r.randn(C, Z3_DIM, HID) * 0.3, jnp.float32),
    }

    def stage_fn(p, x, model_axis=None, comm_overlap=None):
        h = jax.nn.relu(column_parallel(x, p["wi"]["kernel"],
                                        p["wi"]["bias"],
                                        model_axis=model_axis))
        y = row_parallel(h, p["wo"]["kernel"], p["wo"]["bias"],
                         model_axis=model_axis)
        return y + jnp.tanh(y @ p["mix_in"]) @ p["mix_out"]

    def head(outputs, batch):
        return jnp.mean((outputs - batch["y"]) ** 2), {}

    trainable = PipelineTrainable(stage_fn, stacked, head, optax.adam(1e-2),
                                  num_stages=C)
    spec = {"topology": {"platform": "cpu", "num_devices": 8},
            "mesh": {"data": 2, "pipe": 2, "model": 2}}
    if isinstance(collective_precision, tuple):
        collective_precision = dict(collective_precision)
    return AutoDist(spec, "Pipeline", num_microbatches=2,
                    virtual_stages=Z3_V, tensor_parallel=2,
                    zero_stage=zero_stage,
                    collective_precision=collective_precision
                    ).build(trainable)


@functools.lru_cache(maxsize=None)
def zero_step_text(zero_stage: int, collective_precision=None) -> str:
    import jax
    import numpy as np

    r = np.random.RandomState(0)
    batch = {"x": r.randn(8, 8).astype(np.float32),
             "y": r.randn(8, 8).astype(np.float32)}
    runner = zero_runner(zero_stage, collective_precision)
    try:
        return compiled_text(runner.lowered.step_fn, runner.state,
                             runner._place_batch(batch),
                             jax.random.PRNGKey(0))
    finally:
        runner.close()


# --------------------------------------------------------------------------- #
# MoE expert-parallel programs
# --------------------------------------------------------------------------- #
def moe_runner(expert: int = 2, collective_precision=None, kernel=None,
               zero_stage: int = 0):
    """dp×expert MoE LM (mesh {data:2, expert:E}) through the
    ExpertParallel strategy — the dispatch/combine all_to_all pair is
    the program's moe_a2a wire boundary."""
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.models.moe_transformer import (MoeConfig,
                                                     make_moe_lm_trainable)

    cfg = MoeConfig(vocab_size=32, hidden_size=16, num_layers=1,
                    num_heads=2, expert_hidden=32, num_experts=4,
                    max_len=8, dtype=jnp.float32)
    trainable = make_moe_lm_trainable(cfg, optax.adam(1e-2),
                                      jax.random.PRNGKey(0),
                                      batch_size=4, seq_len=8)
    spec = {"topology": {"platform": "cpu", "num_devices": 2 * expert},
            "mesh": {"data": 2, "expert": expert}}
    if isinstance(collective_precision, tuple):
        collective_precision = dict(collective_precision)
    return AutoDist(spec, "ExpertParallel", zero_stage=zero_stage,
                    num_experts=4,
                    collective_precision=collective_precision,
                    kernel=kernel).build(trainable)


@functools.lru_cache(maxsize=None)
def moe_step_text(expert: int = 2, collective_precision=None,
                  kernel=None, zero_stage: int = 0) -> str:
    import jax
    import numpy as np

    r = np.random.RandomState(0)
    x = r.randint(0, 32, (8, 8)).astype(np.int32)
    batch = {"x": x, "y": np.roll(x, -1, axis=1)}
    runner = moe_runner(expert, collective_precision, kernel, zero_stage)
    try:
        return compiled_text(runner.lowered.step_fn, runner.state,
                             runner._place_batch(batch),
                             jax.random.PRNGKey(0))
    finally:
        runner.close()


# --------------------------------------------------------------------------- #
# Elastic reshard programs
# --------------------------------------------------------------------------- #
# Distinctive dim of the resharded matrix (no other tensor dimension
# equals it) and the two layouts the corpus reshard moves between:
# axis-0 shards -> axis-1 shards of the same 8-device data mesh — a
# transition whose every element changes owner, so the compiled route
# is a genuine redistribution (all-to-alls at per-pair payloads), not
# a local relabel.
RS_DIM = 61
RS_ROWS = 64


def _reshard_trainable():
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import Trainable

    r = np.random.RandomState(0)
    params = {"w": jnp.asarray(r.randn(RS_ROWS, RS_DIM) * 0.1,
                               jnp.float32),
              "b": jnp.zeros((RS_DIM,), jnp.float32)}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2) \
            + 0.0 * jnp.sum(p["b"])

    return Trainable.from_loss_fn(loss_fn, params, optax.adam(1e-2))


def _reshard_strategy(split_axis: int):
    from autodist_tpu.strategy.ir import (GraphConfig, NodeConfig,
                                          PartitionerConfig,
                                          PSSynchronizer, Strategy)

    part = "8,1" if split_axis == 0 else "1,8"
    return Strategy(node_configs=[
        NodeConfig("w", PSSynchronizer(),
                   PartitionerConfig(partition_str=part)),
        NodeConfig("b", PSSynchronizer()),
    ], graph_config=GraphConfig(replicas=8))


@functools.lru_cache(maxsize=None)
def _reshard_pair():
    from autodist_tpu import AutoDist

    spec = {"topology": {"platform": "cpu", "num_devices": 8}}
    src = AutoDist(spec).build(_reshard_trainable(),
                               _reshard_strategy(0))
    dst = AutoDist(spec).build(_reshard_trainable(),
                               _reshard_strategy(1))
    return src, dst


def reshard_budget() -> int:
    """The ADT110 gather budget of the corpus reshard: the largest
    per-device stored shard of the TARGET layout."""
    from autodist_tpu.elastic.reshard import shard_budget

    _, dst = _reshard_pair()
    return shard_budget((dst.lowered, dst.state))


@functools.lru_cache(maxsize=None)
def reshard_step_text(naive: bool = False) -> str:
    """Optimized HLO of the corpus reshard program: FSDP axis-0 shards
    re-laid as axis-1 shards on the same 8-device mesh, as the ONE
    compiled program the fast path runs.  ``naive=True`` compiles the
    program a full-materialization staging route produces instead —
    the same transfer with every output replicated first — whose
    full-array gathers the ADT110 reshard rule must catch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu.elastic.reshard import build_convert_fn

    src, dst = _reshard_pair()
    convert, _ = build_convert_fn(src.lowered, src.state, dst.lowered)
    if naive:
        raw = getattr(convert, "__wrapped__", convert)
        replicated = jax.tree.map(
            lambda s: NamedSharding(dst.lowered.mesh, P()),
            dst.lowered.state_shardings)
        fn = jax.jit(raw, out_shardings=replicated)
        return compiled_text(fn, src.state)
    return compiled_text(convert, src.state)


# --------------------------------------------------------------------------- #
# Serving decode programs
# --------------------------------------------------------------------------- #
# Decode-probe geometry: T (cache max_len) and V (vocab) are chosen
# distinctive — no other tensor dimension equals either, so a shape scan
# hit IS the buffer the claim forbids.  The paged pool adds two more
# distinctive extents: DEC_BLOCK_LEN deliberately does NOT divide DEC_T
# (the padded 4·16 = 64 lane the composed gather assembles must differ
# from the 57 extent the ADT115 dense-lane scan keys on), and
# DEC_POOL_BLOCKS (13) is the gather-operand extent no other dimension
# equals.
DEC_T = 57
DEC_V = 93
DEC_LAYERS = 2
DEC_SLOTS = 3
DEC_HEAD_DIM = 8
DEC_BLOCK_LEN = 16
DEC_POOL_BLOCKS = 13


def _serving_engine(tensor_parallel: int = 1, vocab_parallel: bool = False,
                    kernel=None, kv_layout: str = "dense",
                    num_slots: int = DEC_SLOTS, prefill_len: int = 8):
    """The toy serving engine whose programs the decode and prefill
    probes read."""
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
    from autodist_tpu.models.transformer import TransformerConfig
    from autodist_tpu.serving import ServingEngine

    cfg = TransformerConfig(vocab_size=DEC_V, hidden_size=16,
                            num_layers=DEC_LAYERS, num_heads=2,
                            mlp_dim=32, max_len=DEC_T, dtype=jnp.float32,
                            dropout_rate=0.0, attention_dropout_rate=0.0)
    params = make_pipeline_lm_trainable(
        cfg, optax.sgd(0.1), jax.random.PRNGKey(0)).params
    return ServingEngine(cfg, params, tensor_parallel=tensor_parallel,
                         vocab_parallel=vocab_parallel, kernel=kernel,
                         num_slots=num_slots, max_len=DEC_T,
                         prefill_len=prefill_len, decode_steps=4,
                         kv_layout=kv_layout,
                         kv_block_len=DEC_BLOCK_LEN,
                         kv_num_blocks=DEC_POOL_BLOCKS)


@functools.lru_cache(maxsize=None)
def decode_step_text(tensor_parallel: int, vocab_parallel: bool,
                     kernel=None, kv_layout: str = "dense") -> str:
    """Optimized HLO of one fused-decode dispatch of the serving
    engine (memoized like the pipeline texts)."""
    return _serving_engine(tensor_parallel, vocab_parallel, kernel,
                           kv_layout).compiled_decode_text()


# The prefill probe's own distinctive extents: no other dimension of
# the program equals the slot count or the prompt bucket.
PRE_SLOTS = 5
PRE_LEN = 11


@functools.lru_cache(maxsize=None)
def prefill_step_text(kv_layout: str = "dense") -> str:
    """Optimized HLO of the serving engine's single-shot prefill
    program, one dispatch of which admits one row."""
    return _serving_engine(kv_layout=kv_layout, num_slots=PRE_SLOTS,
                           prefill_len=PRE_LEN).compiled_prefill_text()
