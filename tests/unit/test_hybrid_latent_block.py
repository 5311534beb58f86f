"""A stack that mixes delta-rule layers whose decay is a vector over the
key channels (Kimi Delta Attention) with latent-attention layers, under
a routed FFN whose sigmoid router keeps some of its expert groups: two
kinds of state, a float32 recurrent matrix a slot and a latent row a
position, in ONE cache manager.

The program — ``sequential_logits``, and the engine's prefill then fused
decode through the cache manager — against the benchmark's plain
reference (``benchmark/reference/ling-3.0-flash.py``, which shares no
code with the program: position-by-position recurrence, expanded
attention over the whole prefix, the held experts in a plain loop) at a
small size with seeded weights in float32; the pieces on their own (the
chunked form against the recurrence with the decay at its floor, the
state kernel against the composed step, the router's group rule, the
shares against the whole); the planted faults; and the engine options
such a block refuses, each by name.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.models.transformer import (BlockSpec, LatentAttentionSpec,
                                             LinearMixerSpec, RoutedFFNSpec)
from autodist_tpu.parallel import moe
from autodist_tpu.serving import ServingEngine, kv_cache

# the loader, seeded weights (a ``scale`` about 1, everything else about 0
# — the decay's ``dt_bias`` too, which the benchmark leaves 0: a dropped
# one shows), ragged requests, the engine under a batcher and the widest
# gap to the reference: test_hybrid_block's; a shape tree as a trainable:
# test_latent_block's
from tests.unit.test_hybrid_block import (_bench, _fill, _gap, _requests,
                                          _serve)
from tests.unit.test_latent_block import _Shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "ling-3.0-flash"

# Float32 on both sides: what separates the program's logits from the
# reference's is the order of float32 sums (the chunked form against the
# recurrence, absorbed against expanded products, sorted groups against a
# loop over experts) through 12 layers.  Measured here at most 3e-5 on
# logits of size ~3; every planted fault moves logits by 0.03 and more.
LOGIT_TOL = 3e-4


@pytest.fixture(scope="module")
def bench():
    return _bench()


@pytest.fixture(scope="module")
def ref(bench):
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def plants(bench):
    return bench.load_module("tools", "planted_hybrid_latent").PLANTS


@pytest.fixture(scope="module")
def rc(bench):
    """The configuration file at its rehearsal size: two periods at
    width 64, 16 experts in 4 groups of which one group is held, 2
    groups kept, 4 a token, float32."""
    spec = bench.benchmark_spec()
    return bench.sized(bench.config_of(spec, {"name": NAME,
                                              "config": NAME}), True)


def _cfg_of(bench, rc):
    return bench.load_module(
        "builders", "hybrid_latent_moe_lm_serving").transformer_config(rc)


@pytest.fixture(scope="module")
def cfg(bench, rc):
    return _cfg_of(bench, rc)


@pytest.fixture(scope="module")
def params(ref, rc, cfg):
    out = _fill(ref.param_shapes(rc))
    # the program's own shape function agrees on the tree
    assert jax.tree.map(jnp.shape, out) == lm.param_shapes(cfg)
    return out


# --------------------------------------------------------------------- #
# the whole model against the plain reference
# --------------------------------------------------------------------- #
def test_the_block_is_the_published_one(cfg):
    spec = cfg.block
    assert spec.layer_kinds(12) == (("linear",) * 5 + ("latent",)) * 2
    assert spec.linear.gate == "channel" and spec.linear.gate_floor == -5.0
    assert spec.linear.key_heads == spec.linear.value_heads
    assert (spec.moe.scores, spec.moe.groups, spec.moe.groups_kept,
            spec.moe.scale, spec.moe.correction) == ("sigmoid", 4, 2, 2.5,
                                                     True)
    assert spec.attn_gate and spec.rope_interleave and spec.dense_layers == 2


@pytest.mark.parametrize("length", [1, 5, 23, 40])
def test_sequential_logits_match_the_reference(ref, rc, cfg, params, length):
    tokens = jax.random.randint(jax.random.PRNGKey(length), (2, length), 0,
                                cfg.vocab_size)
    got = lm.sequential_logits(cfg, params, tokens)
    want = ref.forward(params, tokens, rc)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_a_window_longer_than_a_chunk_matches_the_reference(ref, rc, cfg,
                                                            params):
    """150 positions: three chunks of the chunked form, the decay carried
    from one to the next."""
    wide = dataclasses.replace(cfg, max_len=256)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, 150), 0,
                                cfg.vocab_size)
    got = lm.sequential_logits(wide, params, tokens)
    want = ref.forward(params, tokens, rc)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_prefill_then_decode_through_the_cache(ref, rc, cfg, params):
    """Ragged admissions on three slots, every slot reused after an
    eviction: each served token is the reference's first choice at its
    position, over the whole of every request; the manager says what it
    holds of each kind of state, and the router what its groups did."""
    telemetry.reset()
    requests = _requests()
    served = _serve(cfg, params, requests)
    assert [len(t) for _, t in served] == [o for _, o in requests]
    assert _gap(ref, rc, params, served) <= LOGIT_TOL
    counts = {m["name"]: m["value"]
              for m in telemetry.get().registry.snapshot() if "value" in m}
    assert 0 < counts["moe/rows_held"] <= counts["moe/rows_routed"]
    assert counts["moe/experts_hit"] <= counts["moe/rows_held"]
    # a row reaches a held expert only through its kept group; the chip's
    # one group is kept by about half the rows (2 of 4)
    assert 0 < counts["moe/groups_hit"] <= counts["moe/rows_routed"] / 4
    assert counts["moe/rows_held"] <= 4 * counts["moe/groups_hit"]
    assert counts["engine/state_rows"] > 0
    assert counts["serve/latent_positions_read"] > 0
    assert counts["kv/latent_layers"] == 2 and counts["kv/linear_layers"] == 10
    assert counts["engine/kv_bytes_per_token"] == 2 * (32 + 8) * 4
    assert counts["kv/row_bytes"] == 3 * 48 * 2 * (32 + 8) * 4
    per_slot = 10 * (3 * 3 * 64 * 4 + 4 * 16 * 16 * 4)
    assert counts["engine/state_bytes_per_slot"] == per_slot
    assert counts["kv/state_bytes"] == 3 * per_slot


@pytest.mark.parametrize("plant", [
    "scalar_gate", "stale_state", "groups_unlimited", "correction_weighs",
    "no_head_gate", "row_short", "share_offset"])
def test_a_planted_fault_reads_far_above_a_sound_run(ref, rc, bench, params,
                                                     plants, plant):
    """Each fault of ``benchmark/tools/planted_hybrid_latent.py`` under
    the engine: one gate a head in place of one a channel, a state not
    overwritten at admission, no group left out, the correction used as
    a weight, the heads' gates dropped, a row read one position short, a
    share offset by one."""
    with plants[plant]():
        served = _serve(_cfg_of(bench, rc), params, _requests())
    # a sound run reads at most 3e-5; the mildest of these (the row read
    # short, in 2 layers of 12) 0.02
    assert _gap(ref, rc, params, served) > 50 * LOGIT_TOL


def _admit(cfg, params, bucket, prompt, padding=0):
    """``(first token, the slot's [conv, ssm], its rows)`` after the
    one-row prefill of ``prompt`` in a ``bucket``-wide row padded with
    ``padding``."""
    engine = ServingEngine(cfg, params, num_slots=2, max_len=48,
                           prefill_len=bucket, decode_steps=4)
    prompts = np.full((2, bucket), padding, np.int32)
    prompts[1, :len(prompt)] = prompt
    toks = engine.prefill(prompts, np.array([0, len(prompt)]),
                          np.array([False, True]))
    return (int(toks[1]),
            [np.asarray(a)[:, 1] for a in engine._state_args()],
            np.asarray(engine.cache.k)[:, 1, 0, :len(prompt)])


@pytest.mark.parametrize("p_len", [1, 3, 9, 16])
def test_padding_leaves_both_kinds_of_state_bit_for_bit(cfg, params, p_len):
    """The same prompt in the same bucket, padded with token 0 or with
    token 77: the recurrent state and the convolution tail of every
    linear layer, the live rows of both latent layers and the first
    token are the same bits."""
    prompt = np.random.default_rng(p_len).integers(0, cfg.vocab_size, p_len)
    first, state, rows = _admit(cfg, params, 16, prompt)
    first77, state77, rows77 = _admit(cfg, params, 16, prompt, padding=77)
    assert first == first77
    assert rows.shape == (2, p_len, 40) and np.abs(rows).sum() > 0
    assert rows.tobytes() == rows77.tobytes()
    for a, b in zip(state, state77):
        assert a.tobytes() == b.tobytes()


def test_an_admission_overwrites_the_slot_and_no_other(cfg, params):
    """The one-row prefill writes the state from blank and the rows at
    the slot's lane: the other slot's state and rows keep their bits."""
    engine = ServingEngine(cfg, params, num_slots=2, max_len=48,
                           prefill_len=16, decode_steps=4)
    prompts = np.zeros((2, 16), np.int32)
    prompts[0, :5], prompts[1, :9] = np.arange(5) + 3, np.arange(9) + 40
    engine.prefill(prompts, np.array([5, 0]), np.array([True, False]))
    before = [np.asarray(a)[:, 0].copy() for a in engine._state_args()]
    rows = np.asarray(engine.cache.k)[:, 0].copy()
    engine.prefill(prompts, np.array([5, 9]), np.array([False, True]))
    for a, b in zip(before, engine._state_args()):
        assert a.tobytes() == np.asarray(b)[:, 0].tobytes()
        assert np.abs(np.asarray(b)[:, 1]).sum() > 0
    assert rows.tobytes() == np.asarray(engine.cache.k)[:, 0].tobytes()
    assert list(engine.lengths) == [5, 9]


def test_the_state_is_float32_whatever_the_activations(cfg, params):
    """bf16 weights, activations and rows: the recurrent matrices stay
    float32 (the benchmark's weights forget fast, so its comparison on
    the chip may let a bf16 state pass: this assert holds it), and the
    state kernel refuses anything else."""
    from autodist_tpu.kernel.pallas.delta_step import delta_step_fits

    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    engine = ServingEngine(half, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), params), num_slots=2, max_len=48,
        prefill_len=16, decode_steps=4)
    assert engine.cache.state.ssm.dtype == jnp.float32
    assert engine.cache.state.conv.dtype == jnp.bfloat16
    assert engine.cache.k.dtype == jnp.bfloat16
    engine.prefill(np.ones((2, 16), np.int32), np.array([4, 0]),
                   np.array([True, False]))
    engine.decode(np.array([True, False]))
    assert engine.cache.state.ssm.dtype == jnp.float32
    assert delta_step_fits((10, 96, 32, 128, 128), jnp.float32)
    assert not delta_step_fits((10, 96, 32, 128, 128), jnp.bfloat16)


# --------------------------------------------------------------------- #
# the recurrence with a decay a key channel
# --------------------------------------------------------------------- #
def _recurrence_inputs(T, B=2, heads=3, dk=16, dv=8, seed=0, floor=None):
    """``g`` ``[B, T, heads, dk]`` in (-5, 0); with ``floor`` every
    channel of head 0 sits at ``floor`` for the whole window and head
    1's channels alternate between it and nearly 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = lm._l2_normalise(jax.random.normal(ks[0], (B, T, heads, dk))) \
        * dk ** -0.5
    k = lm._l2_normalise(jax.random.normal(ks[1], (B, T, heads, dk)))
    v = jax.random.normal(ks[2], (B, T, heads, dv))
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(
        ks[3], (B, T, heads, dk)))
    if floor is not None:
        g = g.at[:, :, 0].set(floor)
        g = g.at[:, :, 1, ::2].set(floor).at[:, :, 1, 1::2].set(-1e-3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, heads)))
    state = jax.random.normal(ks[5], (B, heads, dk, dv))
    return q, k, v, g, beta, state


def _token_by_token(q, k, v, g, beta, state):
    """The recurrence as the reference writes it: decay the rows, delta
    from the decayed state, write, read."""
    out = []
    for t in range(q.shape[1]):
        S = state * jnp.exp(g[:, t])[..., None]
        d = (v[:, t] - jnp.einsum("bhkv,bhk->bhv", S, k[:, t])) \
            * beta[:, t][..., None]
        state = S + jnp.einsum("bhk,bhv->bhkv", k[:, t], d)
        out.append(jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))
    return jnp.stack(out, 1), state


@pytest.mark.parametrize("T,chunk", [(1, 64), (5, 16), (37, 16), (37, 32),
                                     (64, 64), (70, 64), (150, 64)])
def test_chunked_form_is_the_recurrence(T, chunk):
    """Lengths that are no multiple of the chunk, and one that is."""
    args = _recurrence_inputs(T)
    o, state = lm.gated_delta_chunked(*args, chunk=chunk)
    want_o, want_state = _token_by_token(*args)
    np.testing.assert_allclose(o, want_o, atol=3e-6, rtol=0)
    np.testing.assert_allclose(state, want_state, atol=3e-6, rtol=0)


@pytest.mark.parametrize("floor", [-5.0, -4.0, -1.0])
def test_chunked_form_at_the_floor_for_whole_chunks(floor):
    """Three whole chunks with a head's every channel at the floor (5 x
    64 = 320: ``exp`` of the chunk's summed decays negated would
    overflow float32 at 88) and a head whose channels alternate between
    the floor and almost none: finite, and the recurrence's."""
    args = _recurrence_inputs(192, floor=floor, seed=1)
    o, state = jax.jit(lm.gated_delta_chunked)(*args)
    want_o, want_state = _token_by_token(*args)
    assert np.isfinite(o).all() and np.isfinite(state).all()
    np.testing.assert_allclose(o, want_o, atol=3e-6, rtol=0)
    np.testing.assert_allclose(state, want_state, atol=3e-6, rtol=0)


def test_no_exp_of_more_than_a_subchunk(monkeypatch):
    """Every ``exp`` the chunked form takes has an argument of at most 0:
    no decay is ever divided out over more than nothing."""
    largest = []
    real = jnp.exp
    monkeypatch.setattr(jnp, "exp", lambda x: (largest.append(float(
        jnp.max(x))), real(x))[1])
    lm.gated_delta_chunked(*_recurrence_inputs(128, floor=-5.0))
    assert largest and max(largest) <= 0.0


def test_one_step_is_the_recurrence():
    q, k, v, g, beta, state = _recurrence_inputs(1)
    o, new = lm.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], state)
    want_o, want = _token_by_token(q, k, v, g, beta, state)
    np.testing.assert_allclose(o, want_o[:, 0], atol=1e-6, rtol=0)
    np.testing.assert_allclose(new, want, atol=1e-6, rtol=0)


def test_a_vector_of_equal_decays_is_the_scalar_gate():
    """One recurrence for both gates: a decay a channel, every channel
    alike, gives what the head's one decay gives."""
    q, k, v, g, beta, state = _recurrence_inputs(40)
    one = g[..., :1]
    wide = jnp.broadcast_to(one, g.shape)
    for fn in (lm.gated_delta_chunked, _token_by_token):
        o_s, s_s = lm.gated_delta_chunked(q, k, v, one[..., 0], beta, state)
        o_v, s_v = fn(q, k, v, wide, beta, state)
        np.testing.assert_allclose(o_v, o_s, atol=3e-6, rtol=0)
        np.testing.assert_allclose(s_v, s_s, atol=3e-6, rtol=0)


def test_a_masked_position_leaves_the_state_bit_for_bit():
    q, k, v, g, beta, state = _recurrence_inputs(24)
    live = (jnp.arange(24) < 9)[None, :, None]
    _, short = lm.gated_delta_chunked(q[:, :9], k[:, :9], v[:, :9],
                                      g[:, :9], beta[:, :9], state)
    _, padded = lm.gated_delta_chunked(q, k, v, g * live[..., None],
                                       beta * live, state)
    assert np.asarray(short).tobytes() == np.asarray(padded).tobytes()


# --------------------------------------------------------------------- #
# the state kernel: one for both gates
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("gate", ["head", "channel"])
@pytest.mark.parametrize("heads", [8, 16])
def test_state_kernel_is_the_composed_step(gate, heads):
    """The in-place kernel under the interpreter against the composed
    step on the layer's slice: a decay a head (the same column
    broadcast) and a decay a row of each tile."""
    from autodist_tpu.kernel.pallas.delta_step import gated_delta_step_fused

    B, dk, dv, L, layer = 3, 128, 128, 3, 1
    ks = jax.random.split(jax.random.PRNGKey(heads), 7)
    q = lm._l2_normalise(jax.random.normal(ks[0], (B, heads, dk))) \
        * dk ** -0.5
    k = lm._l2_normalise(jax.random.normal(ks[1], (B, heads, dk)))
    v = jax.random.normal(ks[2], (B, heads, dv))
    shape = (B, heads, dk) if gate == "channel" else (B, heads)
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, heads)))
    ssm = jax.random.normal(ks[5], (L, B, heads, dk, dv))
    want_o, want = lm.gated_delta_step(q, k, v, g, beta, ssm[layer])
    o, out = gated_delta_step_fused(q, k, v, g, beta, ssm, layer,
                                    interpret=True)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=0)
    np.testing.assert_allclose(out[layer], want, atol=2e-6, rtol=0)
    for other in (0, 2):        # no other layer's tile is touched
        assert np.asarray(out[other]).tobytes() \
            == np.asarray(ssm[other]).tobytes()


def test_the_layout_elects_the_state_kernel_for_both_kinds_of_state():
    """``LatentLayout`` built with ``recurrent=`` holds rows beside
    state, advances the state through the same seam as a dense lane's,
    and says what a request costs of each."""
    mixer = LinearMixerSpec(8, 8, 128, 128, gate="channel", gate_floor=-5.0)
    dims = (2, 3, 1, 576, 64)
    layout = kv_cache.LatentLayout(dims, {"delta_step": True}, kv_rank=512,
                                   scale=0.07, recurrent=(5, mixer))
    cache = layout.init_cache(dims, jnp.bfloat16)
    assert cache.k.shape == (2, 3, 1, 64, 576) and cache.v.size == 0
    assert cache.state.ssm.shape == (5, 3, 8, 128, 128)
    assert cache.state.ssm.dtype == jnp.float32
    assert cache.state.conv.shape == (5, 3, 3, 3 * 8 * 128)
    assert layout.state_kernel(cache.state.ssm)
    assert layout.accounting() == (0, 0, 0)
    assert layout.reserve(cache, 0, 4, 4)[0] is cache
    assert layout.release(cache, 0) is cache
    assert layout.protect(cache, np.ones(3, bool), 4) is cache
    held = kv_cache.bytes_held(dims, jnp.bfloat16, (5, mixer), arrays=1)
    assert held == {"kv_bytes_per_token": 2 * 576 * 2,
                    "state_bytes_per_slot": 5 * (3 * 3072 * 2
                                                 + 8 * 128 * 128 * 4)}
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k = (jax.random.normal(ks[i], (3, 8, 128)) * 0.1 for i in (0, 1))
    v = jax.random.normal(ks[2], (3, 8, 128))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (3, 8, 128)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (3, 8)))
    ssm = jax.random.normal(ks[5], cache.state.ssm.shape)
    o, out = layout.advance_state(q, k, v, g, beta, ssm, 2)
    plain = kv_cache.LatentLayout(dims, {"delta_step": False}, kv_rank=512,
                                  scale=0.07, recurrent=(5, mixer))
    want_o, want = plain.advance_state(q, k, v, g, beta, ssm, 2)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=0)
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=0)


# --------------------------------------------------------------------- #
# the router: sigmoid scores, a correction that chooses, groups
# --------------------------------------------------------------------- #
def _router_inputs(R=40, H=32, E=16, seed=0, spread=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (R, H)),
            jax.random.normal(ks[1], (H, E)) * 0.3,
            jax.random.normal(ks[2], (E,)) * spread)


RULE = dict(scores="sigmoid", groups=4, groups_kept=2, scale=2.5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_route_is_the_references(ref, rc, seed):
    x, w, c = _router_inputs(seed=seed, H=64, spread=0.2)
    experts, weights = moe.route_top_k(x, w, 4, True, correction=c, **RULE)
    p = {"router": {"kernel": w, "correction": c}}
    want_e, want_w = ref.route(x, p, ref._sizes(rc), rc)
    assert np.array_equal(np.sort(experts, -1), np.sort(want_e, -1))
    np.testing.assert_allclose(np.sort(weights, -1), np.sort(want_w, -1),
                               atol=1e-6)


def test_a_tokens_experts_lie_in_the_kept_groups():
    x, w, c = _router_inputs(R=200)
    experts, weights = moe.route_top_k(x, w, 4, True, correction=c, **RULE)
    groups = np.asarray(experts) // 4
    assert max(len(set(row)) for row in groups) <= 2
    # ... which an unlimited top-4 of 16 does not keep to
    free, _ = moe.route_top_k(x, w, 4, True, correction=c, scores="sigmoid",
                              scale=2.5)
    assert max(len(set(row)) for row in np.asarray(free) // 4) > 2
    np.testing.assert_allclose(weights.sum(-1), 2.5, atol=1e-5)
    # the kept groups are the two whose best two corrected scores sum
    # highest
    s = np.asarray(jax.nn.sigmoid(x @ w) + c).reshape(200, 4, 4)
    best = np.argsort(-np.sort(s, -1)[..., -2:].sum(-1), -1)[:, :2]
    assert all(set(g) <= set(b) for g, b in zip(groups, best))


def test_the_correction_chooses_and_does_not_weigh():
    x, w, c = _router_inputs(R=200, spread=0.5)
    with_e, with_w = moe.route_top_k(x, w, 4, True, correction=c, **RULE)
    none_e, none_w = moe.route_top_k(x, w, 4, True, correction=0 * c, **RULE)
    assert not np.array_equal(with_e, none_e)       # it chooses
    # the weights are the plain scores of whatever was chosen
    s = np.asarray(jax.nn.sigmoid(x @ w))
    picked = np.take_along_axis(s, np.asarray(with_e), -1)
    np.testing.assert_allclose(
        with_w, 2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6)
    # a correction that lifts one expert above all puts it in every row
    # and leaves its weight its own score's
    lift = jnp.zeros_like(c).at[5].set(10.0)
    e, wts = moe.route_top_k(x, w, 4, True, correction=lift, **RULE)
    assert (np.asarray(e) == 5).any(-1).all()
    got = np.take_along_axis(np.asarray(wts), np.argmax(
        np.asarray(e) == 5, -1)[:, None], -1)[:, 0]
    chosen = np.take_along_axis(s, np.asarray(e), -1)
    np.testing.assert_allclose(got, 2.5 * s[:, 5] / chosen.sum(-1),
                               atol=1e-6)


def test_the_softmax_router_is_untouched():
    x, w, _ = _router_inputs()
    experts, weights = moe.route_top_k(x, w, 3, False)
    probs = jax.nn.softmax(x @ w, -1)
    want_w, want_e = jax.lax.top_k(probs, 3)
    assert np.array_equal(experts, want_e)
    np.testing.assert_allclose(weights, want_w, atol=1e-6)
    _, stats = moe.routed_experts(
        x, w, jnp.zeros((4, 32, 16)), jnp.zeros((4, 8, 32)), top_k=3)
    assert stats.shape == (2,)


def test_the_shares_add_up_to_the_uncut_layer(ref, rc, cfg, params):
    """The guide's share test: the four chips' routed parts (a group of
    4 of the 16 experts each) plus the shared expert once are what the
    uncut layer gives — by the reference handed all 16 experts."""
    stages = params["stages"]
    held = rc["num_experts"]
    chips = rc["num_experts_published"] // held
    seeds = jax.random.split(jax.random.PRNGKey(5), chips)
    wi = jnp.concatenate([jax.random.normal(s, (held, 64, 64)) * 0.11
                          for s in seeds])
    wo = jnp.concatenate([jax.random.normal(s, (held, 32, 64)) * 0.11
                          for s in seeds])
    nth = 3
    moe_params = jax.tree.map(
        lambda a: a[nth], {k: v for k, v in stages["moe"].items()
                           if k != "experts"})
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 64))
    total, shared_alone = 0.0, None
    hit = []
    for chip in range(chips):
        at = slice(chip * held, (chip + 1) * held)
        spec = dataclasses.replace(cfg.block.moe, first_expert=chip * held)
        part = dataclasses.replace(cfg, block=dataclasses.replace(
            cfg.block, moe=spec))
        y, stats = lm.routed_ffn(part, dict(
            moe_params, experts={"wi": wi[at], "wo": wo[at]}), h)
        hit.append(int(stats[2]))
        if shared_alone is None:
            # what every chip computes alike: the shared expert, from a
            # chip whose experts are all zero
            shared_alone, _ = lm.routed_ffn(part, dict(
                moe_params, experts={"wi": 0 * wi[at], "wo": 0 * wo[at]}), h)
        total = total + (y - shared_alone)
    total = total + shared_alone
    whole_rc = dict(rc, num_experts=rc["num_experts_published"])
    z = ref._sizes(whole_rc)
    identity = lambda t: t
    want = ref._moe(h, dict(moe_params, experts={"wi": wi, "wo": wo}), z,
                    whole_rc, identity, identity)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    # every row keeps 2 of the 4 groups: each chip's group is kept by
    # some rows, and the four counts add up to two a row
    assert all(hit) and sum(hit) == 2 * 18


# --------------------------------------------------------------------- #
# latent attention as a kind of layer: its gate, its rotary pairs
# --------------------------------------------------------------------- #
def test_interleaved_rotary_is_rotate_half_of_permuted_columns():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 3, 8))
    pos = jnp.arange(7) + 3
    got = lm.rope(x, pos, 1e4, interleave=True)
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    want = lm.rope(halves, pos, 1e4)
    np.testing.assert_allclose(got[..., 0::2], want[..., :4], atol=1e-6)
    np.testing.assert_allclose(got[..., 1::2], want[..., 4:], atol=1e-6)


def test_absorbed_and_expanded_agree_under_the_heads_gate(cfg, params):
    """A latent layer of the mixed stack (layer 5, the first of its
    kind): one position against cached rows in the absorbed form gives
    what the expanded form gives at that position, gate and all; without
    the gate both change."""
    chunk = lm.layer_chunk(cfg, params["stages"], 5)
    assert "latent_attention" in chunk and "gate" in chunk["latent_attention"]
    T = 9
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 64))
    mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    want, rows = lm.latent_expanded(cfg, chunk, x, jnp.arange(T), mask)

    def attend(q, row):
        lanes = rows.at[:, T - 1].set(row[:, 0, 0])[:, None]
        out = kv_cache.cached_attention(
            q, lanes, lanes, jnp.full((2,), T - 1), dtype=jnp.float32,
            scale=cfg.block.latent_softmax_scale)
        return out[..., :cfg.block.latent.kv_rank], None

    got, _ = lm.latent_absorbed(cfg, chunk, x[:, -1:],
                                jnp.full((2, 1), T - 1), attend)
    np.testing.assert_allclose(got[:, 0], want[:, -1], atol=2e-5, rtol=0)
    plain = dataclasses.replace(cfg, block=dataclasses.replace(
        cfg.block, attn_gate=False))
    ungated, _ = lm.latent_expanded(plain, chunk, x, jnp.arange(T), mask)
    assert float(jnp.abs(ungated - want).max()) > 1e-2


@pytest.mark.parametrize("change,message", [
    (dict(layer_period=("linear", "latent"), latent=None), "'latent' layers"),
    (dict(layer_period=("full", "latent")), "'full' and 'latent'"),
    (dict(layer_period=("linear", "full")), "'latent' layers"),
    (dict(layer_period=("linear", "window")), "kinds are"),
    (dict(qk_norm=True), "latent attention has its own head sizes"),
    (dict(latent=None, layer_period=("linear",), attn_gate=False),
     "rope_interleave"),
])
def test_block_spec_refuses_what_it_cannot_mean(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(cfg.block, **change)


@pytest.mark.parametrize("change,message", [
    (dict(gate="row"), "one of"),
    (dict(gate="channel", gate_floor=0.0), "gate_floor"),
    (dict(gate="head", gate_floor=-5.0), "gate_floor"),
])
def test_linear_mixer_spec_refuses(change, message):
    with pytest.raises(ValueError, match=message):
        LinearMixerSpec(4, 4, 16, 16, **change)


@pytest.mark.parametrize("change", [
    dict(scores="tanh"), dict(groups=3), dict(groups=4, groups_kept=5),
    dict(groups=4, groups_kept=1, top_k=5), dict(groups=16, groups_kept=8)])
def test_routed_spec_refuses(change):
    kw = dict(num_experts=16, top_k=4, expert_width=8, experts_held=4)
    kw.update(change)
    with pytest.raises(ValueError):
        RoutedFFNSpec(**kw)


def test_an_all_latent_stack_is_a_period_of_one():
    """``layer_period == ()`` with ``latent``: every layer is latent, the
    block DeepSeek-V2-Lite's configuration has said since PR 35."""
    spec = BlockSpec(norm="rmsnorm", norm_placement="pre", positions="rope",
                     ffn="swiglu", bias=False, tied_head=False,
                     latent=LatentAttentionSpec(32, 16, 8, 16))
    assert spec.layer_kinds(3) == ("latent",) * 3
    assert BlockSpec().layer_kinds(2) == ("full", "full")


# --------------------------------------------------------------------- #
# what lives on the block table refuses this block, by name
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kw,message", [
    (dict(kv_layout="paged"), "paged KV over recurrent state"),
    (dict(kv_layout="paged", prefix_caching=True),
     "prefix caching over recurrent state"),
    (dict(kv_layout="paged", prefill_chunk=16),
     "chunked prefill over recurrent state"),
    (dict(speculative=2), "speculative verify over recurrent state"),
    (dict(tensor_parallel=2), "latent KV row"),
])
def test_engine_refuses_by_name(cfg, params, kw, message):
    with pytest.raises(ValueError, match=message):
        ServingEngine(cfg, params, num_slots=2, max_len=48, prefill_len=16,
                      **kw)


def test_the_handoff_refuses_by_name(cfg, params):
    from autodist_tpu.serving.disagg import check_handoff_block

    engine = ServingEngine(cfg, params, num_slots=2, max_len=48,
                           prefill_len=16)
    with pytest.raises(ValueError, match="recurrent state"):
        check_handoff_block(engine)
    engine.linear_layers = 0
    with pytest.raises(ValueError, match="latent KV row"):
        check_handoff_block(engine)


def test_decode_cost_prices_both_kinds_of_state(cfg):
    """The simulator's decode step: the 2 latent layers pay for their
    rows (not 12 layers' keys and values), the 10 linear ones for their
    state there and back."""
    from autodist_tpu.resource import ResourceSpec
    from autodist_tpu.simulator import CostModel

    spec = ResourceSpec({"topology": {"platform": "tpu",
                                      "generation": "v5e",
                                      "num_devices": 1}})
    cm, model, slots = CostModel(spec), _Shapes(cfg), 8
    tp1 = {"tensor_parallel": 1}
    priced = cm.decode_cost(model, tp1, batch_slots=slots, max_len=64,
                            block=cfg.block)
    lat, lin = cfg.block.latent, cfg.block.linear
    assert priced.attn_time_s == pytest.approx(
        2 * lat.row * 2 * 64 * slots / 819e9)
    state = 4 * 10 * lin.value_heads * lin.key_dim * lin.value_dim
    assert priced.state_time_s == pytest.approx(2 * state * slots / 819e9)
    all_latent = dataclasses.replace(cfg.block, layer_period=(), linear=None)
    rows_only = cm.decode_cost(model, tp1, batch_slots=slots, max_len=64,
                               block=all_latent)
    assert rows_only.attn_time_s == pytest.approx(6 * priced.attn_time_s)
    assert rows_only.state_time_s == 0


# --------------------------------------------------------------------- #
# the schema gate holds what such an engine reports
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("doctor,says", [
    (lambda recs: recs.pop(1), "are counted with what it routed"),
    (lambda recs: recs[4].update(value=0), "only through a group"),
    (lambda recs: recs[4].update(value=900), "only through a group"),
    (lambda recs: recs.pop(), "come together and positive"),
    (lambda recs: recs[-1].update(value=0), "come together and positive"),
    (lambda recs: None, None),
], ids=["groups-alone", "held-without-a-group", "groups-over-routed",
        "three-gauges", "no-state-bytes", "sound"])
def test_schema_gate_holds_the_groups_and_the_two_states(tmp_path, doctor,
                                                         says):
    import json
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    recs = [{"kind": "counter", "name": "moe/layer_steps", "value": 64},
            {"kind": "counter", "name": "moe/rows_routed", "value": 480},
            {"kind": "counter", "name": "moe/rows_held", "value": 120},
            {"kind": "counter", "name": "moe/experts_hit", "value": 90},
            {"kind": "counter", "name": "moe/groups_hit", "value": 70},
            {"kind": "gauge", "name": "engine/experts_held", "value": 8},
            {"kind": "gauge", "name": "kv/latent_layers", "value": 2},
            {"kind": "gauge", "name": "kv/linear_layers", "value": 10},
            {"kind": "gauge", "name": "kv/row_bytes", "value": 4096},
            {"kind": "gauge", "name": "kv/state_bytes", "value": 8192}]
    doctor(recs)
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in recs)
    with open(tmp_path / "trace.json", "w") as f:
        json.dump({"traceEvents": []}, f)
    problems = telemetry_report.check_schema(str(tmp_path))
    assert (any(says in p for p in problems) if says else not problems), \
        problems
