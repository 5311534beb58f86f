"""A block that is not the post-LN one, run several times: RMSNorm in
sandwich placement, rotary positions, a SiLU-gated FFN, no biases, an
untied head, ``loop_steps`` passes over one set of weights and a KV
cache indexed by (pass, layer).

The engine — prefill, then fused decode through the cache — against the
benchmark's plain reference (``benchmark/reference/ouro-2.6b.py``, which
shares no code with the program) on logits, at a small size with seeded
random weights in float32 at ``highest``; the same comparison has to
fail for a program whose cache is indexed by the layer alone, or that
runs one pass fewer.  Then what the spec leaves alone: the default
block's rehearsal programs lower to the parent's HLO, and the engine
options the new block refuses.
"""
import hashlib
import importlib.util
import os
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.models.transformer import BlockSpec, TransformerConfig
from autodist_tpu.serving import ServingEngine
from tests.unit.test_serving import (ADMIT_SUBSETS, admit_id,
                                     check_prefill_admits, resident_engine)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
VOCAB = 509

# Float32 at ``highest`` on both sides: what separates the engine's
# logits from the reference's is the order of float32 sums (fused qkv,
# the cache's masked softmax over max_len keys against the reference's
# over the sequence) through loop_steps x layers blocks, each of which
# ends in a norm.  Measured here at most 3e-5 on logits of size ~1; the
# limit leaves a factor of ten.  A cache indexed by the layer alone, or
# a pass fewer, moves logits by ~0.1-1.
LOGIT_TOL = 3e-4


def _bench():
    """The benchmark's loader (``benchmark/harness/loader.py``), which
    finds the benchmark's other files by name."""
    path = os.path.join(ROOT, "benchmark", "harness", "loader.py")
    spec = importlib.util.spec_from_file_location("looped_test_loader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench().load_module("reference", "ouro-2.6b")


def _ref_cfg(hidden=64, heads=2, loops=3, layers=2):
    """The reference's configuration, in the published file's keys."""
    return {"hidden_size": hidden, "num_hidden_layers": layers,
            "num_attention_heads": heads, "num_key_value_heads": heads,
            "head_dim": hidden // heads, "intermediate_size": 96,
            "vocab_size": VOCAB, "rms_norm_eps": 1e-6,
            "rope_theta": 1000000, "total_ut_steps": loops,
            "early_exit_threshold": 1, "max_position_embeddings": 64,
            "serving": {"dtype": "float32", "weights_dtype": "float32",
                        "max_len": 32}}


def _spec(loops=3, **kw):
    return BlockSpec(norm="rmsnorm", norm_placement="sandwich",
                     positions="rope", rope_theta=1e6, ffn="swiglu",
                     bias=False, tied_head=False, loop_steps=loops, **kw)


def _cfg(rc, loops=None):
    return TransformerConfig(
        vocab_size=rc["vocab_size"], hidden_size=rc["hidden_size"],
        num_layers=rc["num_hidden_layers"],
        num_heads=rc["num_attention_heads"],
        mlp_dim=rc["intermediate_size"],
        max_len=rc["max_position_embeddings"], dtype=jnp.float32,
        dropout_rate=0.0, attention_dropout_rate=0.0,
        block=_spec(rc["total_ut_steps"] if loops is None else loops))


def _params(ref, rc, seed=0):
    """Seeded weights in the tree the program consumes (the reference's
    ``param_shapes``): matrices normal x 0.11 (the root of 2048 / 64
    times the published 0.02, so that a layer moves the stream as much
    as at full width), norm scales drawn about 1 so that a misplaced or
    missing norm shows."""
    def fill(tree, path):
        made = {}
        for name in sorted(tree):
            v = tree[name]
            if isinstance(v, dict):
                made[name] = fill(v, path + (name,))
                continue
            shape, _ = v
            key = jax.random.fold_in(jax.random.PRNGKey(seed), zlib.crc32(
                "/".join(path + (name,)).encode()) & 0x7FFFFFFF)
            x = jax.random.normal(key, shape, jnp.float32)
            made[name] = 1.0 + 0.2 * x if name == "scale" else 0.11 * x
        return made

    out = fill(ref.param_shapes(rc), ())
    # the program's own shape function agrees on the tree (it leaves
    # the exit gate out of a stack that is not looped)
    want = lm.param_shapes(_cfg(rc))
    want["shared"].setdefault("exit_gate", {
        "kernel": (rc["hidden_size"],), "bias": ()})
    assert jax.tree.map(jnp.shape, out) == want
    return out


def _serve(cfg, params, prompts, p_lens, steps=8, **engine_kw):
    """Prefill the prompts and decode ``steps`` tokens through the
    cache: ``[B, 1 + steps]`` token ids."""
    eng = ServingEngine(cfg, params, num_slots=len(prompts), max_len=32,
                        prefill_len=prompts.shape[1], decode_steps=4,
                        **engine_kw)
    admit = np.ones(len(prompts), bool)
    for slot, n in enumerate(p_lens):       # a no-op for a dense cache
        eng.reserve_slot(slot, int(n), steps + 1)
    toks = [eng.prefill(prompts, p_lens, admit)[None]]
    for _ in range(steps // 4):
        toks.append(eng.decode(admit))
    return np.concatenate(toks, 0).T


def _gaps(ref, rc, params, prompts, p_lens, served):
    """At every served position, how far the served token's reference
    logit lies below the reference's best (teacher-forced)."""
    worst = []
    for i, n in enumerate(p_lens):
        seq = np.concatenate([prompts[i, :n], served[i, :-1]])
        logits, steps, _ = ref.forward(params, jnp.asarray(seq[None]), rc)
        at = np.asarray(logits[0, n - 1:])
        assert (np.asarray(steps) == rc["total_ut_steps"]).all()
        worst.append(float((at.max(-1) - at[np.arange(len(at)),
                                            served[i]]).max()))
    return max(worst)


def _traffic(seed=0, batch=3, width=8):
    r = np.random.default_rng(seed)
    return (r.integers(0, VOCAB, (batch, width)),
            np.array([5, width, 3][:batch]))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------------------- #
# the program against the plain reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("hidden,heads", [(64, 2), (256, 2)],
                         ids=["heads32", "heads128"])
def test_full_forward_matches_the_reference_on_logits(ref, hidden, heads):
    """``sequential_logits`` (the program's full forward: the layer
    function prefill runs) against the reference's, logit for logit."""
    rc = _ref_cfg(hidden, heads)
    params = _params(ref, rc)
    prompts, _ = _traffic()
    want = ref.forward(params, jnp.asarray(prompts), rc)[0]
    got = lm.sequential_logits(_cfg(rc), params, jnp.asarray(prompts))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("hidden,heads,kernel", [
    (64, 2, None), (256, 2, None),
    (64, 2, {"flash_decode": True}),     # [d, block] tiles, interpreted
    (256, 2, {"flash_decode": True}),    # [block, d] tiles: heads of 128
], ids=["heads32", "heads128", "heads32-kernel", "heads128-kernel"])
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        ref, hidden, heads, kernel):
    rc = _ref_cfg(hidden, heads)
    params = _params(ref, rc)
    prompts, p_lens = _traffic()
    served = _serve(_cfg(rc), params, prompts, p_lens, kernel=kernel)
    assert _gaps(ref, rc, params, prompts, p_lens, served) <= LOGIT_TOL


def test_one_pass_equals_the_unlooped_stack(ref):
    """``loop_steps`` 1 with the same weights: the layers once, the
    final norm in the head — the program's unlooped path (no
    ``fori_loop``) against the reference run for one loop step."""
    rc = _ref_cfg(loops=1)
    params = _params(ref, rc)
    prompts, p_lens = _traffic()
    want = ref.forward(params, jnp.asarray(prompts), rc, loops=1)[0]
    got = lm.sequential_logits(_cfg(rc), params, jnp.asarray(prompts))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)
    served = _serve(_cfg(rc), params, prompts, p_lens)
    assert _gaps(ref, rc, params, prompts, p_lens, served) <= LOGIT_TOL
    # ... and three passes are not one
    three = lm.sequential_logits(_cfg(rc, loops=3), params,
                                 jnp.asarray(prompts))
    assert float(jnp.abs(three - got).max()) > 100 * LOGIT_TOL


def test_cache_indexed_by_the_layer_alone_fails(ref, monkeypatch):
    """Every pass writing and reading layer ``l``'s rows — a cache of
    ``num_layers`` layers — serves other tokens: a later pass's rows
    overwrite the earlier one's, which the next step's earlier pass
    then attends over."""
    real = ServingEngine._run_layers

    def by_layer_alone(self, shared, stages, x, kc, vc, layer_fn):
        return real(self, shared, stages, x, kc, vc,
                    lambda chunk, x, kc, vc, l, _: layer_fn(
                        chunk, x, kc, vc, l, l))

    monkeypatch.setattr(ServingEngine, "_run_layers", by_layer_alone)
    rc = _ref_cfg()
    params = _params(ref, rc)
    prompts, p_lens = _traffic()
    served = _serve(_cfg(rc), params, prompts, p_lens)
    assert _gaps(ref, rc, params, prompts, p_lens, served) > 10 * LOGIT_TOL


def test_one_pass_fewer_fails(ref):
    rc = _ref_cfg()
    params = _params(ref, rc)
    prompts, p_lens = _traffic()
    served = _serve(_cfg(rc, loops=2), params, prompts, p_lens)
    assert _gaps(ref, rc, params, prompts, p_lens, served) > 10 * LOGIT_TOL


@pytest.mark.parametrize("engine_kw", [
    dict(kv_layout="paged", kv_block_len=4),
    dict(kv_layout="paged", kv_block_len=4, prefill_chunk=4),
    dict(kv_layout="paged", kv_block_len=4,
         kernel={"flash_decode": True}),
], ids=["paged", "paged-chunked-prefill", "paged-kernel"])
def test_other_cache_layouts_serve_the_new_block(ref, engine_kw):
    """Paged KV and chunked prefill run the same layer pieces with the
    (pass, layer) cache index: they agree with the reference too."""
    rc = _ref_cfg()
    params = _params(ref, rc)
    prompts, p_lens = _traffic()
    served = _serve(_cfg(rc), params, prompts, p_lens, **engine_kw)
    assert _gaps(ref, rc, params, prompts, p_lens, served) <= LOGIT_TOL


# --------------------------------------------------------------------- #
# the one-row prefill of the looped block (helpers: test_serving.py)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module", params=[
    dict(), dict(kv_layout="paged", kv_block_len=5)],
    ids=["dense", "paged"])
def resident_looped(request, ref):
    rc = _ref_cfg()
    with jax.default_matmul_precision("highest"):
        return resident_engine(_cfg(rc), _params(ref, rc), **request.param)


@pytest.mark.parametrize("admit", ADMIT_SUBSETS, ids=admit_id)
def test_looped_prefill_computes_and_writes_only_admitted_slots(
        resident_looped, ref, admit):
    """Every pass's keys and values of an admitted row, cache layer by
    cache layer, and nothing of any other slot."""
    rc = _ref_cfg()
    check_prefill_admits(resident_looped, _cfg(rc), _params(ref, rc),
                         admit)


# --------------------------------------------------------------------- #
# what the engine says of itself, and what it refuses
# --------------------------------------------------------------------- #
def test_cache_holds_every_pass_and_says_so(ref):
    rc = _ref_cfg()
    telemetry.reset()
    try:
        eng = ServingEngine(_cfg(rc), _params(ref, rc), num_slots=2,
                            max_len=32, prefill_len=8)
        gauges = {m["name"]: m["value"]
                  for m in telemetry.get().registry.snapshot()
                  if m["kind"] == "gauge"}
        eng.prefill(np.zeros((2, 8), np.int64), np.array([3, 3]),
                    np.ones(2, bool))
        eng.decode(np.ones(2, bool))
        spans = {e["name"]: e.get("args", {}) for e in
                 telemetry.get().chrome_trace()["traceEvents"]}
    finally:
        telemetry.reset()
    assert eng.cache.k.shape == (3 * 2, 2, 2, 32, 32)
    assert gauges["engine/cache_layers"] == 6
    # keys and values, 6 layers, 2 heads of 32, 4 bytes
    assert gauges["engine/kv_bytes_per_token"] == 2 * 6 * 2 * 32 * 4
    assert spans["engine/prefill/dispatch"]["loop_steps"] == 3
    assert spans["engine/decode/dispatch"]["loop_steps"] == 3


def test_engine_refuses_what_it_does_not_run(ref):
    rc = _ref_cfg()
    params = _params(ref, rc)
    cfg = _cfg(rc)
    with pytest.raises(ValueError, match="exit_threshold=0.9.*adaptive"):
        ServingEngine(
            TransformerConfig(**{**cfg.__dict__,
                                 "block": _spec(exit_threshold=0.9)}),
            params, num_slots=2, max_len=32, prefill_len=8)
    with pytest.raises(ValueError,
                       match="tensor_parallel=2 with a non-default block"):
        ServingEngine(cfg, params, tensor_parallel=2, num_slots=2,
                      max_len=32, prefill_len=8)
    with pytest.raises(ValueError, match="trains the default block only"):
        import optax
        lm.make_pipeline_lm_trainable(cfg, optax.sgd(0.1),
                                      jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="BlockSpec.norm='batchnorm'"):
        BlockSpec(norm="batchnorm")


def test_looped_decode_program_passes_the_kernel_rules(ref):
    """The forced kernel under the loop: the decode window of the looped
    heads-of-128 engine keeps the program rules' contract (one fused
    loop, donated caches aliased, no score square, no collective) and
    wears the ``adtk_flash_decode`` marker where ``cached_attention``
    stood."""
    from autodist_tpu.analysis import lint_program, rules_for_decode

    rc = _ref_cfg(256, 2)
    eng = ServingEngine(_cfg(rc), _params(ref, rc), num_slots=2, max_len=32,
                        prefill_len=8, decode_steps=4,
                        kernel={"flash_decode": True})
    text = eng.compiled_decode_text()
    assert "adtk_flash_decode" in text
    rules = [r for r in rules_for_decode(
        1, False, vocab_size=VOCAB, max_len=32, num_layers=2, num_slots=2,
        heads_local=2, head_dim=128, kernel=("flash_decode",))
        if r.code != "ADT111"]   # min_dus: the kernel writes the rows
    report = lint_program(text, rules, where="decode/looped")
    assert not report.errors, [d.to_dict() for d in report.errors]


def test_engine_gives_its_memory_back_without_the_cycle_collector(ref):
    """A caller that wrapped the engine's entry points and restored them
    (the benchmark's runner) drops the engine, and parameters and cache
    go with the last reference: on a frozen heap (``gc.freeze``) nothing
    else would free them."""
    import gc
    import weakref

    rc = _ref_cfg()
    eng = ServingEngine(_cfg(rc), _params(ref, rc), num_slots=2,
                        max_len=32, prefill_len=8)
    real = eng.prefill
    eng.prefill = lambda *a, **kw: real(*a, **kw)
    eng.prefill(np.zeros((2, 8), np.int64), np.array([3, 3]),
                np.ones(2, bool))
    eng.decode(np.ones(2, bool))
    eng.prefill = real
    assert "prefill" not in vars(eng)
    gone = weakref.ref(eng)
    gc.disable()
    try:
        del eng, real
        assert gone() is None
    finally:
        gc.enable()


def test_decode_cost_prices_every_pass():
    """``loop_steps`` multiplies the stacked layers' FLOPs, the
    attention term and the KV elements of a position — not the
    parameters' bytes; 1 prices what it priced."""
    import optax
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import CostModel

    cfg = TransformerConfig(vocab_size=512, hidden_size=64, num_layers=2,
                            num_heads=2, mlp_dim=128, max_len=64,
                            dropout_rate=0.0, attention_dropout_rate=0.0)
    trainable = lm.make_pipeline_lm_trainable(cfg, optax.sgd(0.1),
                                              jax.random.PRNGKey(0))
    cm = CostModel(ResourceSpec({"topology": {"platform": "cpu",
                                              "num_devices": 2}}))
    one = cm.decode_cost(trainable, {"tensor_parallel": 1}, max_len=512)
    assert cm.decode_cost(trainable, {"tensor_parallel": 1}, max_len=512,
                          loop_steps=1) == one
    four = cm.decode_cost(trainable, {"tensor_parallel": 1}, max_len=512,
                          loop_steps=4)
    assert four.kv_bytes_per_device == 4 * one.kv_bytes_per_device
    assert four.attn_time_s == pytest.approx(4 * one.attn_time_s)
    params = one.mem_bytes_per_device - one.kv_bytes_per_device
    assert four.mem_bytes_per_device - four.kv_bytes_per_device == params
    # the layers' matmuls four times, embedding and head once
    stacked = sum(v.size for v in trainable.var_infos()
                  if v.name.startswith("stages/"))
    total = sum(v.size for v in trainable.var_infos())
    assert (four.compute_time_s - four.attn_time_s) / (
        one.compute_time_s - one.attn_time_s) == pytest.approx(
        (3 * stacked + total) / total)
    assert four.request_capacity == pytest.approx(one.request_capacity / 4)
    with pytest.raises(ValueError, match="loop_steps must be >= 1"):
        cm.decode_cost(trainable, {"tensor_parallel": 1}, loop_steps=0)


# --------------------------------------------------------------------- #
# the default block is left alone
# --------------------------------------------------------------------- #
# sha256 of the optimized HLO of ``gpt2-large-postln``'s rehearsal
# programs (CPU backend, this installation), metadata and the frame
# tables cut: read on the parent commit of PR 26 (e038533) with the same
# function.  A PR that means to change these programs reads them anew:
# PR 27 made the prefill the one-row program and read it on its own tree
# (the decode program is still PR 26's parent's).
PARENT_HLO = {
    "decode": "dfdf116c2b672fb9",
    "prefill": "1a653c13b1447b4f",
}


def _program_text(text):
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"stack_frame_id=\d+", "", text)
    head = text[:text.index("FileNames")]
    return head + text[text.index("\n\n", text.index("StackFrames")):]


def test_default_block_programs_are_the_parents():
    loader = _bench()
    spec = loader.benchmark_spec()
    cell = loader.find_cell(spec, "gpt2-large-postln.closed-loop")
    cfg = loader.sized(loader.config_of(spec, cell), True)
    shapes = loader.load_module("reference",
                                "gpt2-large-postln").param_shapes(cfg)
    params = jax.tree.map(
        lambda s: jnp.zeros(s[0], s[1]), shapes,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
    with jax.default_matmul_precision("default"):
        engine, _ = loader.load_module(
            "builders", "pipeline_lm_serving").build_serving(cfg, params)
        got = {"decode": engine.compiled_decode_text(),
               "prefill": engine.compiled_prefill_text()}
    assert {k: hashlib.sha256(_program_text(v).encode()).hexdigest()[:16]
            for k, v in got.items()} == PARENT_HLO


# --------------------------------------------------------------------- #
# the cache layouts are left alone
# --------------------------------------------------------------------- #
# The same hash of a small engine's programs under every cache layout
# and every option that threads an operand through them, read on the
# parent commit of PR 30 (99f20d8) with ``_seam_programs`` and
# ``_renumbered`` below: the layouts moved behind
# ``serving/kv_cache.py``'s seam and the programs had to come out as
# they went in.  A PR that means to change one of these programs reads
# it anew.
SEAM_ENGINES = {
    "dense-kernel": dict(kernel={"flash_decode": True}),
    "paged": dict(kv_layout="paged", kv_block_len=4),
    "paged-prefix": dict(kv_layout="paged", kv_block_len=4,
                         prefix_caching=True),
    "paged-chunked": dict(kv_layout="paged", kv_block_len=4,
                          prefill_chunk=8),
    "paged-speculative": dict(kv_layout="paged", kv_block_len=4,
                              speculative=2),
    "looped-dense": dict(),
}
SEAM_HLO = {
    "dense-kernel": {"decode": "0d95a530a5ad77b4",
                     "prefill": "081359ed66a56b31"},
    "paged": {"decode": "687cf78b23188c8a",
              "prefill": "71fe0f1618f783ba"},
    "paged-prefix": {"decode": "687cf78b23188c8a",
                     "prefill": "b730e541bebe6051"},
    "paged-chunked": {"decode": "687cf78b23188c8a",
                      "prefill": "abae544c0c152973"},
    "paged-speculative": {"decode": "687cf78b23188c8a",
                          "prefill": "71fe0f1618f783ba",
                          "verify": "f754f3f3fc39941d"},
    "looped-dense": {"decode": "eae2d96467e736ad",
                     "prefill": "7405c0758e01a402"},
}


def _seam_programs(name, ref):
    """``{program: optimized HLO}`` of the small engine ``name``."""
    from tests.unit.test_serving import make_cfg

    kw = dict(SEAM_ENGINES[name])
    if name.startswith("looped"):
        rc = _ref_cfg()
        cfg, params = _cfg(rc), _params(ref, rc)
    else:
        import optax

        cfg = make_cfg(max_len=128)
        params = lm.make_pipeline_lm_trainable(
            cfg, optax.sgd(0.1), jax.random.PRNGKey(0)).params
    if "speculative" in kw:
        kw.update(draft_cfg=cfg, draft_params=params)
    with jax.default_matmul_precision("default"):
        eng = ServingEngine(cfg, params, num_slots=3, max_len=cfg.max_len,
                            prefill_len=16, decode_steps=4, **kw)
        got = {"decode": eng.compiled_decode_text(),
               "prefill": eng.compiled_prefill_text()}
        if eng.speculative is not None:
            c, B = eng.cache, eng.num_slots
            got["verify"] = eng._spec_verify_jit.lower(
                eng.params, c.k, c.v, c.lengths, eng._tok,
                eng.kv.table_arg(c), jnp.asarray(eng._sample_seeds),
                jnp.zeros((B, eng.speculative + 1), jnp.int32),
                jnp.ones((B,), bool)).compile().as_text()
    return got


def _renumbered(text):
    """``_program_text`` with every value's number replaced by its order
    of first appearance: the tracer numbers the ops it later folds, so a
    trace that computes ``lengths + c`` once where the parent's computed
    it twice names the same optimized program differently."""
    seen = {}
    return re.sub(r"%[\w.-]+", lambda m: seen.setdefault(
        m.group(0), f"%v{len(seen)}"), _program_text(text))


@pytest.mark.parametrize("name", sorted(SEAM_ENGINES))
def test_cache_layout_programs_are_the_parents(name, ref):
    got = _seam_programs(name, ref)
    assert {k: hashlib.sha256(_renumbered(v).encode()).hexdigest()[:16]
            for k, v in got.items()} == SEAM_HLO[name]
