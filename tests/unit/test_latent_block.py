"""A stack of latent-attention layers: a cached position is ONE row
``[c | k_pe]`` for all query heads (a normed latent and a rotated
positional key), attended in the expanded form over a prompt and in the
absorbed form over the cache; YaRN-scaled rotary on the positional slice;
a leading dense FFN before routed layers whose top-k weights are used as
the softmax left them, beside ungated shared experts.

The program — ``sequential_logits``, and the engine's expanded prefill
then absorbed fused decode through the cache manager's third layout —
against the benchmark's plain reference
(``benchmark/reference/deepseek-v2-lite.py``, which shares no code with
the program and attends expanded keys and values at every position) at a
small size with seeded weights in float32; the pieces on their own; and
the engine options such a block refuses, each by name.
"""
import dataclasses
import importlib.util
import json
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import serving, telemetry
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.models.transformer import (BlockSpec, LatentAttentionSpec,
                                             RopeScaling)
from autodist_tpu.parallel import moe
from autodist_tpu.serving import ServingEngine, kv_cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "deepseek-v2-lite"

# Float32 on both sides: what separates the program's logits from the
# reference's is the order of float32 sums (the absorbed products against
# the expanded ones, sorted groups against a loop over experts, the
# cache's masked softmax over max_len rows) through 5 layers.  Measured
# here at most 1e-5 on logits of size ~4; a row cached without its
# rotation or its norm, a softmax scale without m ** 2, renormalised
# top-k weights or a shifted share move logits by 0.05 and more.
LOGIT_TOL = 3e-4


def _bench():
    path = os.path.join(ROOT, "benchmark", "harness", "loader.py")
    spec = importlib.util.spec_from_file_location("latent_test_loader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _bench()


@pytest.fixture(scope="module")
def ref(bench):
    return bench.load_module("reference", NAME)


def _file(bench, rehearse):
    spec = bench.benchmark_spec()
    return bench.sized(bench.config_of(spec, {"name": NAME,
                                              "config": NAME}), rehearse)


@pytest.fixture(scope="module")
def rc(bench):
    """The configuration file at its rehearsal size: 5 layers of which 1
    dense, width 64, 4 heads, a latent of 32 with 16 / 8 / 16, 16 experts
    of which 4 are held, 3 a token, float32."""
    return _file(bench, True)


@pytest.fixture(scope="module")
def builder(bench):
    return bench.load_module("builders", "latent_moe_lm_serving")


@pytest.fixture(scope="module")
def cfg(builder, rc):
    return builder.transformer_config(rc)


def _fill(shapes, seed=0, std=0.11):
    """Seeded weights for a shape tree (``(shape, dtype)`` leaves):
    matrices normal x ``std``, a ``scale`` drawn about 1 so that a norm
    left out or misplaced shows."""
    def fill(tree, path):
        made = {}
        for name in sorted(tree):
            v = tree[name]
            if isinstance(v, dict):
                made[name] = fill(v, path + (name,))
                continue
            key = jax.random.fold_in(jax.random.PRNGKey(seed), zlib.crc32(
                "/".join(path + (name,)).encode()) & 0x7FFFFFFF)
            x = jax.random.normal(key, v[0], jnp.float32)
            made[name] = 1.0 + 0.2 * x if name.endswith("scale") else std * x
        return made

    return fill(shapes, ())


@pytest.fixture(scope="module")
def params(ref, rc, cfg):
    out = _fill(ref.param_shapes(rc))
    # the program's own shape function agrees on the tree
    assert jax.tree.map(jnp.shape, out) == lm.param_shapes(cfg)
    return out


def _requests(n=7, seed=3, vocab=509):
    """Ragged prompts and budgets; more of them than slots."""
    r = np.random.default_rng(seed)
    return [(r.integers(0, vocab, int(p)).astype(np.int32), int(o))
            for p, o in zip(r.integers(1, 17, n), r.integers(3, 14, n))]


def _serve(cfg, params, requests, **engine_kw):
    kw = dict(num_slots=3, max_len=48, prefill_len=16, decode_steps=4)
    kw.update(engine_kw)
    engine = ServingEngine(cfg, params, **kw)
    batcher = serving.ContinuousBatcher(engine)
    for i, (prompt, budget) in enumerate(requests):
        batcher.submit(prompt, max_new_tokens=budget, rid=f"r{i}")
    batcher.run()
    return [(p, np.asarray(batcher.completions[f"r{i}"].tokens))
            for i, (p, _) in enumerate(requests)]


def _gap(ref, rc, params, served):
    """The widest distance, over every served token, between the
    reference's best logit at that position and its logit for the token
    the program served (teacher-forced on the served tokens)."""
    worst = 0.0
    for prompt, tokens in served:
        seq = np.concatenate([prompt, tokens[:-1]])
        logits = ref.forward(params, jnp.asarray(seq)[None], rc)[0]
        at = logits[len(prompt) - 1:]
        got = jnp.take_along_axis(at, jnp.asarray(tokens)[:, None], -1)[:, 0]
        worst = max(worst, float((at.max(-1) - got).max()))
    return worst


def _counts():
    return {m["name"]: m["value"]
            for m in telemetry.get().registry.snapshot() if "value" in m}


# --------------------------------------------------------------------- #
# the whole model against the plain reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("length", [1, 5, 23, 40])
def test_sequential_logits_match_the_reference(ref, rc, cfg, params, length):
    tokens = jax.random.randint(jax.random.PRNGKey(length), (2, length), 0,
                                cfg.vocab_size)
    got = lm.sequential_logits(cfg, params, tokens)
    want = ref.forward(params, tokens, rc)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_absorbed_decode_agrees_with_the_expanded_reference(ref, rc, cfg,
                                                            params):
    """Prefill in the expanded form, then decode in the absorbed form
    through the cache, against the reference's full expanded forward at
    every position: ragged admissions on three slots, every slot reused
    after an eviction."""
    telemetry.reset()
    requests = _requests()
    served = _serve(cfg, params, requests)
    assert [len(t) for _, t in served] == [o for _, o in requests]
    assert _gap(ref, rc, params, served) <= LOGIT_TOL
    counts = _counts()
    assert 0 < counts["moe/rows_held"] <= counts["moe/rows_routed"]
    assert counts["moe/experts_hit"] <= counts["moe/rows_held"]
    # the four routed layers count, the leading dense one does not
    assert counts["moe/layer_steps"] % 4 == 0
    assert counts["moe/experts_hit"] <= counts["moe/layer_steps"] * 4
    assert counts["engine/experts_held"] == 4
    assert counts["engine/cache_layers"] == 5
    # one row of 32 + 8 float32 values a layer, not heads x head_dim x 2
    assert counts["engine/kv_bytes_per_token"] == 5 * 40 * 4
    # every decode step reads at least the prompt's rows and at most the
    # lane, in each of the 5 layers
    steps = counts["moe/layer_steps"] / 4
    assert 5 * steps <= counts["serve/latent_positions_read"] \
        <= 5 * steps * 3 * 48


def test_latent_positions_read_counts_the_live_rows(cfg, params):
    """One request alone: a prompt of 5 and 9 tokens.  The first comes
    from the prefill; two windows of 4 steps read 5 + 1 .. 5 + 8 rows in
    each of the 5 layers (the second window's steps past the request's
    end are computed, and counted)."""
    telemetry.reset()
    _serve(cfg, params, [(np.arange(5, dtype=np.int32), 9)])
    assert _counts()["serve/latent_positions_read"] \
        == 5 * sum(range(6, 14))


@pytest.mark.parametrize("plant,moves", [
    ("unrotated_key", "the positional key"), ("latent_unnormed", "norm"),
    ("no_mscale", "scale"), ("renormalised", "weights")])
def test_a_planted_fault_moves_the_logits(bench, ref, rc, builder, params,
                                          plant, moves):
    """The faults the benchmark plants under the cell
    (``benchmark/tools/planted_latent.py``) each move served tokens away
    from the reference's first choice by far more than rounding."""
    planted = bench.load_module("tools", "planted_latent")
    with planted.PLANTS[plant]():
        served = _serve(builder.transformer_config(rc), params,
                        _requests(4))
    assert _gap(ref, rc, params, served) > 30 * LOGIT_TOL, moves


def test_the_reference_is_given_the_same_share(ref, rc, params):
    """Offset by one expert, the reference gives other logits."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, 509)
    here = ref.forward(params, tokens, rc)
    there = ref.forward(params, tokens, rc, first_expert=1)
    assert float(jnp.abs(here - there).max()) > 100 * LOGIT_TOL


# --------------------------------------------------------------------- #
# the cached row
# --------------------------------------------------------------------- #
def _admit(cfg, params, bucket, prompt, padding=0):
    engine = ServingEngine(cfg, params, num_slots=2, max_len=48,
                           prefill_len=bucket, decode_steps=4)
    prompts = np.full((2, bucket), padding, np.int32)
    prompts[1, :len(prompt)] = prompt
    engine.prefill(prompts, np.array([0, len(prompt)]),
                   np.array([False, True]))
    return engine


def _rows(engine, p_len):
    return np.asarray(engine.cache.k)[:, 1, 0, :p_len]


@pytest.mark.parametrize("p_len", [1, 3, 9, 16])
def test_padding_leaves_the_rows_bit_for_bit(cfg, params, p_len):
    """The same prompt in the same bucket, padded with token 0 or with
    token 77: the rows of EVERY layer are the same bits, and so is the
    first token — no padded position reaches a real position's row (the
    causal mask) or a real row's experts."""
    prompt = np.random.default_rng(p_len).integers(0, 509, p_len)
    a, b = (_admit(cfg, params, 16, prompt, pad) for pad in (0, 77))
    assert _rows(a, p_len).tobytes() == _rows(b, p_len).tobytes()
    assert int(a._tok[1]) == int(b._tok[1])


@pytest.mark.parametrize("p_len", [1, 3, 9, 16])
def test_two_buckets_cache_the_same_rows(cfg, params, p_len):
    """The same prompt through ``prefill_len`` 16 and 32 leaves the same
    rows in the slot's lane and the same first token; the other slot's
    lane is not touched.  The first layer's rows, which no attention
    precedes, are the same bits; behind an attention the two programs
    differ by the order in which a product sums over 16 or 32 keys, all
    but ``p_len`` of them exact zeros: float32 rounding, nothing the
    padding wrote."""
    prompt = np.random.default_rng(p_len).integers(0, 509, p_len)
    a, b = (_admit(cfg, params, bucket, prompt) for bucket in (16, 32))
    assert _rows(a, p_len)[0].tobytes() == _rows(b, p_len)[0].tobytes()
    np.testing.assert_allclose(_rows(a, p_len), _rows(b, p_len), atol=1e-5,
                               rtol=0)
    assert np.abs(_rows(a, p_len)).min() > 0
    assert int(a._tok[1]) == int(b._tok[1])
    assert not np.asarray(a.cache.k)[:, 0].any()


def test_a_cached_position_is_one_row(cfg, params):
    """``[layer, slot, 1, max_len, kv_rank + rope_dim]``: one key head
    whose values are its first ``kv_rank`` columns, so the values' array
    is empty; ``bytes_held`` and the gauge say layers x row x
    itemsize."""
    engine = ServingEngine(cfg, params, num_slots=2, max_len=48,
                           prefill_len=16)
    lat = cfg.block.latent
    assert isinstance(engine.kv, kv_cache.LatentLayout)
    assert lat.row == 32 + 8
    assert engine.cache.k.shape == (5, 2, 1, 48, lat.row)
    assert engine.cache.v.shape == (5, 2, 1, 48, 0)
    dims = (5, 2, 1, lat.row, 48)
    assert kv_cache.bytes_held(dims, jnp.float32, arrays=1) == {
        "kv_bytes_per_token": 5 * 40 * 4, "state_bytes_per_slot": 0}
    assert kv_cache.bytes_held(dims, jnp.bfloat16, arrays=1)[
        "kv_bytes_per_token"] == 5 * 40 * 2
    # at the published sizes: 27 layers x 576 x 2 B where expanded keys
    # and values would be 27 x 16 x (192 + 128) x 2 B
    assert kv_cache.bytes_held((27, 64, 1, 576, 3072), jnp.bfloat16,
                               arrays=1)["kv_bytes_per_token"] == 31_104
    assert engine.decode_block_len == 48     # a lane is read whole


def test_the_row_is_the_normed_latent_and_the_rotated_key(cfg, params):
    """What the prefill caches at a position is ``[N(c) | rope(k_pe)]``
    of the layer's input there: computed here from the first layer's
    weights on the embedding alone."""
    prompt = np.array([7, 11, 13, 17, 19], np.int32)
    engine = _admit(cfg, params, 16, prompt)
    chunk = lm.layer_chunk(cfg, params["stages"], 0)
    la, lat, spec = chunk["latent_attention"], cfg.block.latent, cfg.block
    x = params["shared"]["embedding"][prompt]
    h = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + spec.norm_eps) \
        * chunk["ln_attention_in"]["scale"]
    down = h @ la["kv_a"]["kernel"]
    c = down[:, :lat.kv_rank]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + spec.norm_eps) \
        * la["kv_norm"]["scale"]
    k_pe = lm.rope(down[None, :, None, lat.kv_rank:], jnp.arange(5),
                   spec.rope_theta, scaling=spec.rope_scaling)[0, :, 0]
    got = np.asarray(engine.cache.k)[0, 1, 0, :5]
    np.testing.assert_allclose(got[:, :lat.kv_rank], c, atol=1e-5)
    np.testing.assert_allclose(got[:, lat.kv_rank:], k_pe, atol=1e-5)
    # position 0 is not rotated; a later one is
    np.testing.assert_allclose(got[0, lat.kv_rank:], down[0, lat.kv_rank:],
                               atol=1e-5)
    assert np.abs(got[3, lat.kv_rank:] - down[3, lat.kv_rank:]).max() > 1e-3


def test_absorbed_and_expanded_are_one_attention(cfg, params):
    """The two entry points over one layer's weights: the last position
    of a window attended expanded equals that position attended absorbed
    over the rows the window cached."""
    chunk = lm.layer_chunk(cfg, params["stages"], 1)
    T, lat = 11, cfg.block.latent
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, cfg.hidden_size))
    mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    want, rows = lm.latent_expanded(cfg, chunk, x, jnp.arange(T), mask)
    assert rows.shape == (2, T, lat.row)
    lane = jnp.zeros((2, 1, 16, lat.row)).at[:, 0, :T].set(rows)

    def attend(q, row):
        np.testing.assert_allclose(row[:, 0, 0], rows[:, -1], atol=1e-6)
        out = kv_cache.cached_attention(
            q, lane, lane, jnp.full((2,), T - 1), dtype=jnp.float32,
            scale=cfg.block.latent_softmax_scale)
        return out[..., :lat.kv_rank], ()

    got, _ = lm.latent_absorbed(cfg, chunk, x[:, -1:],
                                jnp.full((2, 1), T - 1), attend)
    np.testing.assert_allclose(got[:, 0], want[:, -1], atol=2e-5)


# --------------------------------------------------------------------- #
# YaRN
# --------------------------------------------------------------------- #
def test_yarn_frequencies_at_the_published_sizes(bench, ref, builder):
    """Dimension 64, theta 1e4, factor 40 over 4,096 positions, beta 32
    and 1: frequencies 0..10 are kept, 23..31 divided by 40, a ramp
    between; cos and sin are not scaled; the softmax scale is 192^-1/2
    times m ** 2 with m = 0.1 * 0.707 * ln 40 + 1."""
    cfg = builder.transformer_config(_file(bench, False))
    yarn, lat = cfg.block.rope_scaling, cfg.block.latent
    assert yarn.correction_range(64, 1e4) == (10, 23)
    inv = yarn.inv_freq(64, 1e4)
    f = 1e4 ** (-np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,) and inv.dtype == np.float32
    assert inv[0] == 1.0
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[31], f[31] / 40, rtol=1e-6)
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(inv[16], f[16] / 40 * ramp
                               + f[16] * (1 - ramp), rtol=1e-6)
    assert yarn.cos_sin_scale == 1.0
    assert yarn.attention_mscale == pytest.approx(1.2608, abs=5e-5)
    assert cfg.block.latent_softmax_scale == pytest.approx(0.11472,
                                                           abs=5e-6)
    assert lat.row == 576
    # the reference computes them by the published formulas, on its own
    ref_inv, ref_factor, ref_m = ref.yarn(_file(bench, False))
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert (ref_factor, ref_m) == (1.0, yarn.attention_mscale)


def test_scaled_rope_is_plain_rope_at_the_scaled_frequencies():
    """``rope(.., scaling)`` rotates pair ``i`` by ``position x
    inv_freq_i`` and multiplies cos and sin by the factor; a scaling of
    factor 1 is no scaling."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 3, 8))
    pos = jnp.arange(6)
    none = RopeScaling(factor=1.0, original_max_len=16)
    np.testing.assert_allclose(lm.rope(x, pos, 1e4, scaling=none),
                               lm.rope(x, pos, 1e4), atol=1e-6)
    yarn = RopeScaling(factor=40.0, original_max_len=16, mscale=1.0,
                       mscale_all_dim=0.0)
    inv, scale = yarn.inv_freq(8, 1e4), yarn.cos_sin_scale
    assert scale == pytest.approx(0.1 * np.log(40) + 1)
    ang = np.arange(6)[:, None] * inv[None]                 # [S, 4]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    a, b = np.asarray(x[..., :4]), np.asarray(x[..., 4:])
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1) * scale
    np.testing.assert_allclose(lm.rope(x, pos, 1e4, scaling=yarn), want,
                               atol=1e-5)


# --------------------------------------------------------------------- #
# layers that differ in their feed-forward kind; the router's weights
# --------------------------------------------------------------------- #
def test_the_leading_layer_is_dense_and_has_no_router(cfg, params):
    shapes = lm.param_shapes(cfg)["stages"]
    assert shapes["mlp"]["wi"]["kernel"] == (1, 64, 2 * 96)
    assert shapes["moe"]["router"]["kernel"] == (4, 64, 16)
    assert sorted(shapes["moe"]["experts"]) == [
        lm.layer_key(l) for l in range(1, 5)]
    assert "shared_gate" not in shapes["moe"]
    first = lm.layer_chunk(cfg, params["stages"], 0)
    assert "mlp" in first and "moe" not in first
    for l in range(1, 5):
        chunk = lm.layer_chunk(cfg, params["stages"], l)
        assert "moe" in chunk and "mlp" not in chunk
        np.testing.assert_array_equal(
            chunk["moe"]["router"]["kernel"],
            params["stages"]["moe"]["router"]["kernel"][l - 1])
        assert chunk["moe"]["experts"] \
            is params["stages"]["moe"]["experts"][lm.layer_key(l)]
    # the dense layer's feed-forward runs through no routing: no tally
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 64))
    tally = []
    lm.ffn_residual(cfg, first, x, None, tally=tally)
    assert tally == []
    lm.ffn_residual(cfg, lm.layer_chunk(cfg, params["stages"], 1), x, None,
                    tally=tally)
    assert len(tally) == 1


def test_top_k_weights_are_used_as_the_softmax_left_them():
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    probs = jax.nn.softmax(jnp.matmul(x, router, precision="highest"), -1)
    want_w, want_e = jax.lax.top_k(probs, 6)
    experts, weights = moe.route_top_k(x, router, 6, renormalise=False)
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(weights, want_w, rtol=1e-6)
    assert float(weights.sum(-1).max()) < 1.0
    _, renormed = moe.route_top_k(x, router, 6)
    np.testing.assert_allclose(renormed.sum(-1), 1.0, rtol=1e-6)
    # and the routed sum uses them: the dense sum over all 16 experts
    wi = jax.random.normal(jax.random.PRNGKey(2), (16, 32, 16)) * 0.1
    wo = jax.random.normal(jax.random.PRNGKey(3), (16, 8, 32)) * 0.1
    got, _ = moe.routed_experts(x, router, wi, wo, top_k=6,
                                renormalise=False)
    w_all = jnp.zeros((24, 16)).at[
        jnp.arange(24)[:, None], want_e].set(want_w)
    gu = jnp.einsum("rh,ehm->rem", x, wi, precision="highest")
    out = jnp.einsum("rem,emh->reh", jax.nn.silu(gu[..., :8]) * gu[..., 8:],
                     wo, precision="highest")
    np.testing.assert_allclose(got, (w_all[..., None] * out).sum(1),
                               atol=2e-5)


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(cfg, params, shares):
    """The guide's share test: over ``shares`` devices, each holding
    ``16 / shares`` experts and routing over all 16 with the weights the
    softmax left, the routed parts — with the shared experts, which every
    device computes alike, counted once — add up to what the uncut layer
    gives."""
    E, M, H = 16, cfg.block.moe.expert_width, cfg.hidden_size
    ks = jax.random.split(jax.random.PRNGKey(shares), 3)
    whole = dict(lm.layer_chunk(cfg, params["stages"], 1)["moe"])
    whole["experts"] = {"wi": jax.random.normal(ks[0], (E, H, 2 * M)) * 0.1,
                        "wo": jax.random.normal(ks[1], (E, M, H)) * 0.1}
    h = jax.random.normal(ks[2], (2, 9, H))

    def layer(first, held, **spec_kw):
        spec = dataclasses.replace(cfg.block.moe, experts_held=held,
                                   first_expert=first, **spec_kw)
        c = dataclasses.replace(
            cfg, block=dataclasses.replace(cfg.block, moe=spec))
        part = dict(whole, experts=jax.tree.map(
            lambda w: w[first:first + held], whole["experts"]))
        return lm.routed_ffn(c, part, h)

    uncut, stats = layer(0, E)
    assert int(stats[0]) == 2 * 9 * cfg.block.moe.top_k
    shared = uncut - layer(0, E, shared_width=0)[0]
    assert float(jnp.abs(shared).max()) > 1e-3
    held = E // shares
    parts = [layer(s * held, held) for s in range(shares)]
    total = sum(y - shared for y, _ in parts) + shared
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    assert sum(int(s[0]) for _, s in parts) == int(stats[0])


# --------------------------------------------------------------------- #
# what refuses the block
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kw,names", [
    (dict(kv_layout="paged", kv_block_len=4, prefill_chunk=8),
     "chunked prefill with a latent KV row"),
    (dict(speculative=2), "speculative verify with a latent KV row"),
    (dict(kv_layout="paged", kv_block_len=4, prefix_caching=True),
     "prefix caching with a latent KV row"),
    (dict(kv_layout="paged", kv_block_len=4),
     "paged KV with a latent KV row"),
    (dict(tensor_parallel=2), "tensor_parallel=2 with a latent KV row"),
], ids=["chunked-prefill", "speculative", "prefix-caching", "paged",
        "tensor-parallel"])
def test_engine_options_refuse_the_block_by_name(cfg, params, kw, names):
    with pytest.raises(ValueError, match=names):
        ServingEngine(cfg, params, num_slots=2, max_len=32, prefill_len=8,
                      **kw)


def test_the_forced_decode_kernel_serves_the_composed_tokens(ref, rc, cfg,
                                                             params):
    """``kernel={"flash_decode": True}`` with a latent row: the decode
    attention runs in the latent kernel (interpreted here; a lane of 48
    in blocks of 16, so slots sit on every side of a block's edge), which
    writes the step's rows itself, and serves the composed engine's
    tokens one for one — ragged admissions on three slots, every slot
    reused after an eviction — as close to the reference."""
    requests = _requests()
    telemetry.reset()
    fused = _serve(cfg, params, requests, kernel={"flash_decode": True})
    gauges = _counts()
    composed = _serve(cfg, params, requests)
    assert gauges["kernel/latent_decode_elected"] == 1
    assert gauges["kernel/flash_decode_elected"] == 1
    assert _counts()["kernel/latent_decode_elected"] == 0
    for (_, got), (_, want) in zip(fused, composed):
        np.testing.assert_array_equal(got, want)
    assert _gap(ref, rc, params, fused) <= LOGIT_TOL


def test_disaggregated_hand_off_refuses_the_block(cfg, params):
    from autodist_tpu.serving import disagg

    engine = ServingEngine(cfg, params, num_slots=2, max_len=32,
                           prefill_len=8)
    with pytest.raises(ValueError, match="latent KV row"):
        disagg.check_handoff_block(engine)


def test_a_window_against_cached_rows_is_not_served(cfg, params):
    engine = ServingEngine(cfg, params, num_slots=2, max_len=32,
                           prefill_len=8)
    with pytest.raises(NotImplementedError, match="latent rows"):
        engine.kv.attend_window(None, None, None, 0, None, None,
                                dtype=jnp.float32)


@pytest.mark.parametrize("kw,says", [
    (dict(positions="learned"), "positions='rope'"),
    (dict(kv_heads=2), "kv_heads"),
    (dict(layer_period=("full",)), "layer_period"),
], ids=["learned-positions", "grouped-heads", "a-period"])
def test_block_spec_refuses_what_latent_attention_has_not(kw, says):
    base = dict(norm="rmsnorm", norm_placement="pre", positions="rope",
                ffn="swiglu", bias=False,
                latent=LatentAttentionSpec(32, 16, 8, 16))
    with pytest.raises(ValueError, match=says):
        BlockSpec(**{**base, **kw})
    with pytest.raises(ValueError, match="dense_layers"):
        BlockSpec(dense_layers=1)


# --------------------------------------------------------------------- #
# the cost model prices what such a step moves
# --------------------------------------------------------------------- #
class _Shapes:
    """A stand-in trainable: the variables of a shape tree."""

    def __init__(self, cfg):
        from autodist_tpu.capture import VarInfo
        from autodist_tpu.kernel import common

        self.num_stages = cfg.num_layers
        self._infos = []
        common.tree_from_names(
            jax.tree.map(lambda s: np.zeros(s, np.int8),
                         lm.param_shapes(cfg),
                         is_leaf=lambda x: isinstance(x, tuple)),
            lambda name, leaf: self._infos.append(
                VarInfo(name, tuple(leaf.shape), jnp.bfloat16, False)))

    def var_infos(self):
        return self._infos


@pytest.mark.parametrize("slots", [1, 32])
def test_decode_cost_prices_a_latent_row_and_the_dense_layer(cfg, slots):
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import CostModel, rank_serving

    spec = ResourceSpec({"topology": {"platform": "tpu",
                                      "generation": "v5e",
                                      "num_devices": 1}})
    cm, model = CostModel(spec), _Shapes(cfg)
    tp1 = {"tensor_parallel": 1}
    plain = cm.decode_cost(model, tp1, batch_slots=slots, max_len=64)
    priced = cm.decode_cost(model, tp1, batch_slots=slots, max_len=64,
                            block=cfg.block)
    lat, moe_ = cfg.block.latent, cfg.block.moe
    # a position: 5 layers x one row of 40, not 5 x 2 x hidden 64
    assert priced.kv_bytes_per_device == plain.kv_bytes_per_device \
        * lat.row / (2 * 64)
    # and its read: the rows of every lane once a layer at the HBM rate
    assert priced.attn_time_s == pytest.approx(
        5 * lat.row * 2 * 64 * slots / 819e9)
    # the experts: the chosen share of their FLOPs or the bytes of those
    # hit; everything else — the leading layer's dense FFN among it —
    # every parameter once a row
    experts = sum(v.size for v in model.var_infos()
                  if "/experts/" in v.name)
    rate = 197e12 * cm.link_profile.get("mxu_efficiency", 0.4)
    chosen = moe_.top_k / moe_.num_experts
    want = max(2 * experts * chosen * slots / rate,
               2 * experts * (1 - (1 - chosen) ** slots) / 819e9)
    dense = sum(v.size for v in model.var_infos()
                if "/experts/" not in v.name)
    assert any(v.name.startswith("stages/mlp/") for v in model.var_infos())
    assert priced.compute_time_s - priced.attn_time_s - want \
        == pytest.approx(2 * dense * slots / rate, rel=1e-6)
    ranked = rank_serving(model, spec, [tp1], batch_slots=slots,
                          max_len=64, block=cfg.block)
    assert ranked[0][1] == priced


# --------------------------------------------------------------------- #
# the schema gate holds the counter
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("doctor,says", [
    (lambda recs: recs[0].update(value=24 * 48 * 5 + 1),
     "is over steps x slots"),
    (lambda recs: recs.pop(1), "come together"),
    (lambda recs: recs.pop(), "come together"),
    (lambda recs: None, None),
], ids=["over-the-lanes", "no-windows", "no-gauge", "sound"])
def test_schema_gate_holds_the_latent_counter(tmp_path, doctor, says):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    # 8 steps on 3 slots of 48 positions, 5 layers
    recs = [{"kind": "counter", "name": "serve/latent_positions_read",
             "value": 2980},
            {"kind": "counter", "name": "serve/kv_blocks_resident",
             "value": 24},
            {"kind": "gauge", "name": "engine/latent_lane_rows",
             "value": 48},
            {"kind": "gauge", "name": "engine/cache_layers", "value": 5}]
    doctor(recs)
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in recs)
    with open(tmp_path / "trace.json", "w") as f:
        json.dump({"traceEvents": []}, f)
    problems = telemetry_report.check_schema(str(tmp_path))
    assert (any(says in p for p in problems) if says else not problems), \
        problems
