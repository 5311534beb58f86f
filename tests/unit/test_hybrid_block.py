"""A stack of two layer kinds with a routed FFN: gated-DeltaNet layers
(a recurrent state a slot) closed by a gated softmax-attention layer on
grouped key/value heads, zero-centred RMSNorm before each sub-block,
rotary positions on part of the head, 16 experts of which a device holds
some, a shared expert.

The program — ``sequential_logits``, and the engine's prefill then fused
decode through the one cache manager — against the benchmark's plain
reference (``benchmark/reference/qwen3-next-80b-a3b.py``, which shares no
code with the program: token-by-token recurrence, softmax over the whole
prefix, the held experts in a plain loop) at a small size with seeded
weights in float32; the pieces on their own (chunked form against the
recurrence, the routed layer against a dense sum, the shares against the
whole); and the engine options such a block refuses, each by name.
"""
import dataclasses
import importlib.util
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import serving, telemetry
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.parallel import moe
from autodist_tpu.serving import ServingEngine, kv_cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "qwen3-next-80b-a3b"

# Float32 on both sides: what separates the program's logits from the
# reference's is the order of float32 sums (the chunked form against the
# recurrence, sorted groups against a loop over experts, the cache's
# masked softmax over max_len keys) through 8 layers.  Measured here at
# most 2e-5 on logits of size ~3; a stale state, a dropped convolution
# tail or a shifted share moves logits by 0.1 and more.
LOGIT_TOL = 3e-4


def _bench():
    path = os.path.join(ROOT, "benchmark", "harness", "loader.py")
    spec = importlib.util.spec_from_file_location("hybrid_test_loader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _bench()


@pytest.fixture(scope="module")
def ref(bench):
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def rc(bench):
    """The configuration file at its rehearsal size: two periods at
    width 64, 16 experts of which 8 are held, 4 a token, float32."""
    spec = bench.benchmark_spec()
    return bench.sized(bench.config_of(spec, {"name": NAME,
                                              "config": NAME}), True)


@pytest.fixture(scope="module")
def cfg(bench, rc):
    return bench.load_module(
        "builders", "hybrid_moe_lm_serving").transformer_config(rc)


def _fill(shapes, seed=0, std=0.11):
    """Seeded weights for a shape tree (``(shape, dtype)`` leaves):
    matrices normal x ``std``, a plain ``scale`` drawn about 1 and a
    zero-centred ``weight`` about 0, so that a misplaced ``1 +`` shows."""
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
            made[name] = 1.0 + 0.2 * x if name == "scale" else std * x
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


def test_prefill_then_decode_through_the_cache(ref, rc, cfg, params):
    """Ragged admissions on three slots, every slot reused after an
    eviction: each served token is the reference's first choice at its
    position, over the whole of every request."""
    telemetry.reset()
    requests = _requests()
    served = _serve(cfg, params, requests)
    assert [len(t) for _, t in served] == [o for _, o in requests]
    assert _gap(ref, rc, params, served) <= LOGIT_TOL
    counts = {m["name"]: m["value"]
              for m in telemetry.get().registry.snapshot() if "value" in m}
    assert 0 < counts["moe/rows_held"] <= counts["moe/rows_routed"]
    assert counts["moe/experts_hit"] <= counts["moe/rows_held"]
    assert counts["engine/state_rows"] > 0
    assert counts["engine/experts_held"] == 8
    assert counts["engine/kv_bytes_per_token"] == 2 * 2 * 2 * 32 * 4
    assert counts["engine/state_bytes_per_slot"] == 6 * (
        3 * 128 * 4 + 4 * 16 * 16 * 4)


def test_a_state_not_overwritten_at_admission_fails(ref, rc, cfg, params,
                                                    monkeypatch):
    """An admission whose prefill does not write the slot's state:
    decode goes on from what the slot's previous occupant left."""
    real = kv_cache.write_state
    monkeypatch.setattr(
        kv_cache, "write_state",
        lambda arrays, layer, new, slot=None: tuple(arrays)
        if slot is not None else real(arrays, layer, new, slot))
    served = _serve(cfg, params, _requests())
    assert _gap(ref, rc, params, served) > 100 * LOGIT_TOL


def test_padding_that_reaches_the_state_fails(ref, rc, cfg, params,
                                              monkeypatch):
    """A prefill that runs the recurrence over the bucket's padding
    hands decode a state that has seen tokens the request never sent."""
    real = lm.linear_attention
    monkeypatch.setattr(
        lm, "linear_attention",
        lambda cfg_, chunk, x, state, *, valid=None, length=None, **kw:
        real(cfg_, chunk, x, state, valid=None, length=length, **kw))
    served = _serve(cfg, params, _requests())
    assert _gap(ref, rc, params, served) > 100 * LOGIT_TOL


def test_a_dropped_convolution_tail_fails(ref, rc, cfg, params, monkeypatch):
    """Decode steps whose convolution sees no earlier input."""
    real = kv_cache.read_state

    def no_tail(arrays, layer, slot=None):
        conv, ssm = real(arrays, layer, slot)
        return jnp.zeros_like(conv), ssm

    monkeypatch.setattr(kv_cache, "read_state", no_tail)
    served = _serve(cfg, params, _requests())
    assert _gap(ref, rc, params, served) > 100 * LOGIT_TOL


def _admit(cfg, params, bucket, prompt, padding=0):
    """``(first token, [conv, ssm] of the slot)`` after the one-row
    prefill of ``prompt`` in a ``bucket``-wide row padded with
    ``padding``."""
    engine = ServingEngine(cfg, params, num_slots=2, max_len=48,
                           prefill_len=bucket, decode_steps=4)
    prompts = np.full((2, bucket), padding, np.int32)
    prompts[1, :len(prompt)] = prompt
    toks = engine.prefill(prompts, np.array([0, len(prompt)]),
                          np.array([False, True]))
    return int(toks[1]), [np.asarray(a)[:, 1] for a in engine._state_args()]


def test_a_prompts_pass_hands_the_delta_rule_its_zeros(cfg, params,
                                                        monkeypatch):
    """The engine's prompt path says "no state yet"; the delta rule is
    still handed what it was: the very zeros ``blank_linear_state`` makes
    for one row, an empty convolution tail and an empty matrix."""
    made, seen = [], []
    blank, mixer = lm.blank_linear_state, lm.linear_attention

    def making(cfg, batch):
        made.append(blank(cfg, batch))
        return made[-1]

    def looking(cfg, chunk, x, state, **kw):
        if x.shape[1] == 16:        # the prompt's row, not a decode step
            seen.append(state)
        return mixer(cfg, chunk, x, state, **kw)

    monkeypatch.setattr(lm, "blank_linear_state", making)
    monkeypatch.setattr(lm, "linear_attention", looking)
    _admit(cfg, params, 16, np.arange(5))
    assert seen                                 # traced once a kind
    for state in seen:
        assert any(state is zeros for zeros in made)
        tail, ssm = state
        assert tail.shape == (1, cfg.block.linear.conv_taps - 1,
                              cfg.block.linear.conv_channels)
        assert ssm.shape == (1, *cfg.block.linear.state_shape)
        assert (tail.dtype, ssm.dtype) == (cfg.dtype, jnp.float32)


@pytest.mark.parametrize("p_len", [1, 2, 3, 9, 16])
def test_padding_leaves_the_state_bit_for_bit(cfg, params, p_len):
    """The same prompt in the same bucket, padded with token 0 or with
    token 77: the recurrent state and the convolution tail of EVERY
    linear layer are the same bits, and so is the first token — no
    padded position reaches the recurrence, the tail or a real row's
    experts."""
    prompt = np.random.default_rng(p_len).integers(0, cfg.vocab_size, p_len)
    first, state = _admit(cfg, params, 16, prompt)
    first77, state77 = _admit(cfg, params, 16, prompt, padding=77)
    assert first == first77
    for a, b in zip(state, state77):
        assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("p_len", [1, 2, 3, 9, 16])
def test_two_buckets_leave_the_same_state(cfg, params, p_len):
    """The same prompt admitted through ``prefill_len`` 16 and 32: the
    state and the tail of the linear layers ahead of the first full
    layer are the same bits, whatever the bucket (trailing padded chunks
    add exact zeros).  Behind a full layer the two programs differ by
    the order in which softmax attention sums 16 or 32 keys, all but
    ``p_len`` of them exact zeros: float32 rounding, nothing the padding
    wrote."""
    prompt = np.random.default_rng(p_len).integers(0, cfg.vocab_size, p_len)
    first16, state16 = _admit(cfg, params, 16, prompt)
    first32, state32 = _admit(cfg, params, 32, prompt)
    assert first16 == first32
    ahead = cfg.block.layer_period.index("full")
    for a, b in zip(state16, state32):
        assert a[:ahead].tobytes() == b[:ahead].tobytes()
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


# --------------------------------------------------------------------- #
# the linear mixer's two entry points
# --------------------------------------------------------------------- #
def _recurrence_inputs(T, B=2, heads=3, dk=16, dv=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = lm._l2_normalise(jax.random.normal(ks[0], (B, T, heads, dk))) \
        * dk ** -0.5
    k = lm._l2_normalise(jax.random.normal(ks[1], (B, T, heads, dk)))
    v = jax.random.normal(ks[2], (B, T, heads, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, heads)))
    state = jax.random.normal(ks[5], (B, heads, dk, dv))
    return q, k, v, g, beta, state


def _token_by_token(q, k, v, g, beta, state):
    out = []
    for t in range(q.shape[1]):
        o, state = lm.gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                       beta[:, t], state)
        out.append(o)
    return jnp.stack(out, 1), state


@pytest.mark.parametrize("T,chunk", [(1, 8), (5, 4), (37, 8), (37, 16),
                                     (64, 16), (70, 64), (129, 64)])
def test_chunked_form_is_the_recurrence(T, chunk):
    """Lengths that are no multiple of the chunk, and one that is."""
    args = _recurrence_inputs(T)
    o, state = lm.gated_delta_chunked(*args, chunk=chunk)
    want_o, want_state = _token_by_token(*args)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=0)
    np.testing.assert_allclose(state, want_state, atol=2e-6, rtol=0)


def test_a_masked_position_leaves_the_state_bit_for_bit():
    q, k, v, g, beta, state = _recurrence_inputs(24)
    live = (jnp.arange(24) < 9)[None, :, None]
    _, short = lm.gated_delta_chunked(q[:, :9], k[:, :9], v[:, :9],
                                      g[:, :9], beta[:, :9], state, chunk=8)
    _, padded = lm.gated_delta_chunked(q, k, v, g * live, beta * live,
                                       state, chunk=8)
    assert np.asarray(short).tobytes() == np.asarray(padded).tobytes()


@pytest.mark.parametrize("n", [2, 8, 16, 64])
def test_unit_lower_inverse(n):
    m = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, n, n)), -1) \
        * 0.3
    got = lm._unit_lower_inverse(m)
    np.testing.assert_allclose(got @ (jnp.eye(n) + m),
                               jnp.broadcast_to(jnp.eye(n), m.shape),
                               atol=1e-4)


# --------------------------------------------------------------------- #
# the routed layer
# --------------------------------------------------------------------- #
def _routed_inputs(R=24, H=32, E=16, M=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (R, H)),
            jax.random.normal(ks[1], (H, E)) * 0.5,
            jax.random.normal(ks[2], (E, H, 2 * M)) * 0.2,
            jax.random.normal(ks[3], (E, M, H)) * 0.2)


def _dense_routed(x, router, wi, wo, top_k, only=None):
    """Every expert (or ``only`` that one) over every row, weighted by
    the router's renormalised top-k weight (0 outside it)."""
    probs = jax.nn.softmax(x @ router, -1)
    w, e = jax.lax.top_k(probs, top_k)
    w = w / w.sum(-1, keepdims=True)
    full = jnp.zeros_like(probs).at[jnp.arange(len(x))[:, None], e].set(w)
    if only is not None:
        full = full * (jnp.arange(full.shape[1]) == only)
    M = wo.shape[1]
    h = jnp.einsum("rh,ehm->erm", x, wi)
    y = jnp.einsum("erm,emh->erh", jax.nn.silu(h[..., :M]) * h[..., M:], wo)
    return jnp.einsum("re,erh->rh", full, y)


@pytest.mark.parametrize("top_k", [1, 4, 10])
def test_routed_experts_are_the_dense_sum(top_k):
    x, router, wi, wo = _routed_inputs()
    got, stats = moe.routed_experts(x, router, wi, wo, top_k=top_k)
    np.testing.assert_allclose(got, _dense_routed(x, router, wi, wo, top_k),
                               atol=1e-5)
    assert int(stats[0]) == len(x) * top_k      # every pair is held


def test_a_router_biased_onto_one_expert_loses_no_row():
    """No capacity: all 24 rows choose expert 5 first, and every one of
    them gets its term."""
    x, router, wi, wo = _routed_inputs()
    x = x.at[:, 0].set(40.0)
    router = router.at[0].set(0.0).at[:, 5].set(0.0).at[0, 5].set(1.0)
    # logit 40 on expert 5, the others' as they were but for x[:, 0]
    experts, _ = moe.route_top_k(x, router, 2)
    assert (np.asarray(experts[:, 0]) == 5).all()
    got, stats = moe.routed_experts(x, router, wi, wo, top_k=2)
    np.testing.assert_allclose(got, _dense_routed(x, router, wi, wo, 2),
                               atol=1e-4)
    assert int(stats[0]) == 48 and int(stats[1]) >= 2
    only, _ = moe.routed_experts(x, router, wi[5:6], wo[5:6], top_k=2,
                                 first_expert=5)
    assert (np.abs(np.asarray(only)).sum(-1) > 0).all()


@pytest.mark.parametrize("skewed", [False, True],
                         ids=["pairs-fit-the-bound", "pairs-exceed-it"])
def test_the_bound_on_sorted_pairs_drops_nothing(skewed):
    """96 rows x 4 of 64 experts, 4 of them held: 384 pairs, of which
    even routing lands ~24 here and the layer works through 64 sorted
    pairs; a router that sends every row to the held experts lands all
    384 here, and the layer works through them all."""
    x, router, wi, wo = _routed_inputs(R=96, E=64)
    assert moe._pairs_bound(96 * 4, 4, 64) == 64
    if skewed:
        x = x.at[:, 0].set(40.0)
        router = router.at[0].set(0.0).at[0, 8:12].set(1.0)
    got, stats = jax.jit(lambda *a: moe.routed_experts(
        *a, top_k=4, first_expert=8))(x, router, wi[8:12], wo[8:12])
    probs = jax.nn.softmax(x @ router, -1)
    w, e = jax.lax.top_k(probs, 4)
    w = w / w.sum(-1, keepdims=True)
    want = sum(_dense_routed(x, router, wi, wo, 4, only=ex)
               for ex in range(8, 12))
    np.testing.assert_allclose(got, want, atol=2e-4)
    landed = int(((np.asarray(e) >= 8) & (np.asarray(e) < 12)).sum())
    assert int(stats[0]) == landed
    assert (landed > 64) == skewed


def test_rows_that_are_nobodys_choose_nothing():
    x, router, wi, wo = _routed_inputs()
    valid = jnp.arange(len(x)) % 3 != 0
    got, stats = moe.routed_experts(x, router, wi, wo, top_k=4, valid=valid)
    want = _dense_routed(x, router, wi, wo, 4) * valid[:, None]
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert int(stats[0]) == 4 * int(valid.sum())


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(cfg, params, shares):
    """The guide's share test: over ``shares`` devices, each holding
    ``16 / shares`` experts and routing over all 16, the routed parts —
    with the shared expert, which every device computes alike, counted
    once — add up to what the uncut layer gives."""
    import dataclasses

    E, M, H = 16, cfg.block.moe.expert_width, cfg.hidden_size
    ks = jax.random.split(jax.random.PRNGKey(shares), 3)
    whole = dict(lm.layer_chunk(cfg, params["stages"], 0)["moe"])
    whole["experts"] = {"wi": jax.random.normal(ks[0], (E, H, 2 * M)) * 0.1,
                        "wo": jax.random.normal(ks[1], (E, M, H)) * 0.1}
    h = jax.random.normal(ks[2], (2, 9, H))

    def layer(first, held):
        spec = dataclasses.replace(cfg.block.moe, experts_held=held,
                                   first_expert=first)
        c = dataclasses.replace(
            cfg, block=dataclasses.replace(cfg.block, moe=spec))
        part = dict(whole, experts=jax.tree.map(
            lambda w: w[first:first + held], whole["experts"]))
        return lm.routed_ffn(c, part, h)

    uncut, stats = layer(0, E)
    assert int(stats[0]) == 2 * 9 * cfg.block.moe.top_k
    no_shared = dataclasses.replace(cfg.block.moe, shared_width=0)
    shared = uncut - lm.routed_ffn(
        dataclasses.replace(cfg, block=dataclasses.replace(
            cfg.block, moe=no_shared)), whole, h)[0]
    held = E // shares
    parts = [layer(s * held, held) for s in range(shares)]
    total = sum(y - shared for y, _ in parts) + shared
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    assert sum(int(s[0]) for _, s in parts) == int(stats[0])


def test_the_reference_is_given_the_same_share(ref, rc, params):
    """Offset by one expert, the reference gives other logits."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, 509)
    here = ref.forward(params, tokens, rc)
    there = ref.forward(params, tokens, rc, first_expert=1)
    assert float(jnp.abs(here - there).max()) > 100 * LOGIT_TOL


# --------------------------------------------------------------------- #
# grouped heads through the cache
# --------------------------------------------------------------------- #
def test_cached_attention_groups_query_heads():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, n, kv, T, d = 3, 8, 2, 12, 16
    q = jax.random.normal(ks[0], (B, 1, n, d))
    k = jax.random.normal(ks[1], (B, kv, T, d))
    v = jax.random.normal(ks[2], (B, kv, T, d))
    lengths = jnp.array([0, 5, 11])
    got = kv_cache.cached_attention(q, k, v, lengths)
    want = kv_cache.cached_attention(q, jnp.repeat(k, n // kv, 1),
                                     jnp.repeat(v, n // kv, 1), lengths)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_the_recurrence_stays_float32_beside_bf16_activations(cfg):
    """The holder's matrix and a step's new state are float32 whatever
    the activations' type (on the chip a bf16 state reads no worse than
    a sound run against the benchmark's limits: PERF.md section 7)."""
    lin = cfg.block.linear
    held = kv_cache.init_state(6, 3, lin, jnp.bfloat16)
    assert held.ssm.dtype == jnp.float32 and held.conv.dtype == jnp.bfloat16
    tail, ssm = lm.blank_linear_state(
        dataclasses.replace(cfg, dtype=jnp.bfloat16), 3)
    assert ssm.dtype == jnp.float32 and tail.dtype == jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k = (jax.random.normal(key, (3, lin.value_heads, lin.key_dim))
            for key in ks[:2])
    v = jax.random.normal(ks[2], (3, lin.value_heads, lin.value_dim))
    gate = jnp.full((3, lin.value_heads), 0.5)
    _, after = lm.gated_delta_step(q, k, v, -gate, gate, ssm)
    assert after.dtype == jnp.float32


# --------------------------------------------------------------------- #
# what refuses such a block, by name
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kw,names", [
    (dict(kv_layout="paged", kv_block_len=4, prefill_chunk=8),
     "chunked prefill"),
    (dict(speculative=2), "speculative verify"),
    (dict(kv_layout="paged", kv_block_len=4, prefix_caching=True),
     "prefix caching"),
    (dict(kv_layout="paged", kv_block_len=4), "paged KV"),
    (dict(tensor_parallel=2), "tensor_parallel"),
], ids=["chunked-prefill", "speculative", "prefix-caching", "paged",
        "tensor-parallel"])
def test_engine_options_refuse_the_block_by_name(cfg, params, kw, names):
    with pytest.raises(ValueError, match=names):
        ServingEngine(cfg, params, num_slots=2, max_len=32, prefill_len=8,
                      **kw)


@pytest.mark.parametrize("max_len,block", [(256, 128), (48, 48)],
                         ids=["whole-cache", "a-lane-of-one-block"])
def test_the_fused_decode_kernel_serves_the_composed_tokens(cfg, params,
                                                            max_len, block):
    """The full layers' grouped query heads through the fused decode
    kernel (forced: the interpreter) — over the whole cache in blocks of
    128, and as one block where none reads the lane in place — serve the
    greedy tokens of ``cached_attention``, request for
    request, beside the linear layers' state."""
    requests = _requests(n=5)
    long = dataclasses.replace(cfg, max_len=256)    # rotary: no table
    served = {}
    for word in (False, True):
        engine_kw = dict(max_len=max_len, kernel={"flash_decode": word})
        assert ServingEngine(long, params, num_slots=3, prefill_len=16,
                             **engine_kw).kv.fused_block == (
            block if word else None)
        served[word] = _serve(long, params, requests, **engine_kw)
    for (_, fused), (_, plain) in zip(served[True], served[False]):
        np.testing.assert_array_equal(fused, plain)


def test_grouped_heads_alone_refuse_the_block_table(cfg, params):
    """Without a single linear layer the paged readers still take one
    key/value head a query head."""
    import dataclasses

    block = dataclasses.replace(cfg.block, layer_period=(), linear=None,
                                moe=None)
    grouped = dataclasses.replace(cfg, block=block)
    with pytest.raises(ValueError, match="grouped-query"):
        ServingEngine(grouped, params, kv_layout="paged", kv_block_len=4)


def test_disaggregated_hand_off_refuses_the_block(cfg, params):
    from autodist_tpu.serving import disagg

    engine = ServingEngine(cfg, params, num_slots=2, max_len=32,
                           prefill_len=8)
    with pytest.raises(ValueError, match="recurrent state"):
        disagg.check_handoff_block(engine)


# --------------------------------------------------------------------- #
# the cost model prices what such a step moves
# --------------------------------------------------------------------- #
class _Shapes:
    """A stand-in trainable: the variables of a shape tree."""

    num_stages = None

    def __init__(self, cfg):
        from autodist_tpu.capture import VarInfo
        from autodist_tpu.kernel import common

        self.num_stages = cfg.num_layers
        shapes = lm.param_shapes(cfg)
        self._infos = []
        common.tree_from_names(
            jax.tree.map(lambda s: np.zeros(s, np.int8), shapes,
                         is_leaf=lambda x: isinstance(x, tuple)),
            lambda name, leaf: self._infos.append(
                VarInfo(name, tuple(leaf.shape), jnp.bfloat16, False)))

    def var_infos(self):
        return self._infos


@pytest.mark.parametrize("slots", [1, 32])
def test_decode_cost_prices_experts_hit_and_state(cfg, slots):
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
    lin, moe_ = cfg.block.linear, cfg.block.moe
    # the state: 6 linear layers x 4 heads x 16 x 16 float32 a slot,
    # there and back a token at the HBM rate; held in memory a slot
    state = 4 * 6 * lin.value_heads * lin.key_dim * lin.value_dim
    assert priced.state_time_s == pytest.approx(
        2 * state * slots / (819e9))
    # keys and values: 2 full layers x 2 heads x 32, not 8 layers x 64
    assert priced.kv_bytes_per_device == plain.kv_bytes_per_device \
        * (2 * 2 * 32) / (8 * 64)
    params = plain.mem_bytes_per_device - plain.kv_bytes_per_device
    assert priced.mem_bytes_per_device == pytest.approx(
        params + priced.kv_bytes_per_device + state * slots)
    # the experts: the chosen share of their FLOPs or the bytes of
    # those hit, not every one of them once
    experts = sum(v.size for v in model.var_infos()
                  if "/experts/" in v.name)
    rate = 197e12 * cm.link_profile.get("mxu_efficiency", 0.4)
    chosen = moe_.top_k / moe_.num_experts
    want = max(2 * experts * chosen * slots / rate,
               2 * experts * (1 - (1 - chosen) ** slots) / 819e9)
    rest = priced.compute_time_s - priced.attn_time_s \
        - priced.state_time_s - want
    dense = sum(v.size for v in model.var_infos()
                if "/experts/" not in v.name)
    assert rest == pytest.approx(2 * dense * slots / rate, rel=1e-6)
    ranked = rank_serving(model, spec, [tp1], batch_slots=slots,
                          max_len=64, block=cfg.block)
    assert ranked[0][1] == priced


# --------------------------------------------------------------------- #
# the schema gate holds the routing counters
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("doctor,says", [
    (lambda recs: recs.pop(0), "come together"),
    (lambda recs: recs.pop(), "come together"),           # the gauge
    (lambda recs: recs[2].update(value=500), "a pair that is held"),
    (lambda recs: recs[3].update(value=130), "an expert that is hit"),
    (lambda recs: (recs[0].update(value=2), recs[3].update(value=17)),
     "is over moe/layer_steps"),
    (lambda recs: None, None),
], ids=["one-counter", "no-gauge", "held-over-routed", "hit-over-held",
        "hit-over-capacity", "sound"])
def test_schema_gate_holds_the_routing_counters(tmp_path, doctor, says):
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
            {"kind": "gauge", "name": "engine/experts_held", "value": 8}]
    doctor(recs)
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in recs)
    with open(tmp_path / "trace.json", "w") as f:
        json.dump({"traceEvents": []}, f)
    problems = telemetry_report.check_schema(str(tmp_path))
    assert (any(says in p for p in problems) if says else not problems), \
        problems
