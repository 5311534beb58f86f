"""A stack whose every layer mixes by power retention: attention's own
grouped q, k and v (a norm a head, rotary) feeding a gated recurrent
state of the key's symmetric square a key/value head, and a cache
manager that holds no keys and values at all.

The pieces on their own (the symmetric square's identity; the
recurrence, the chunked form and the attention form against each other
at a gate near 0 and near -5; the state kernel under the interpreter
against the composed step; a rung's padding is
``tests/unit/test_prefill_rungs.py``'s, which takes this block as one
more family), then the program —
``sequential_logits``, and the engine's prefill then fused decode through
the cache manager — against the benchmark's plain reference
(``benchmark/reference/brumby-14b-base.py``, which shares no code with
the program and has no state at all) at the rehearsal's size with seeded
weights in float32; the planted faults; and the engine options such a
block refuses, each by name.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.models.transformer import BlockSpec, LinearMixerSpec
from autodist_tpu.serving import ServingEngine, kv_cache

# the loader, seeded weights (a ``scale`` about 1, everything else about
# 0), ragged requests, the engine under a batcher and the widest gap to
# the reference: test_hybrid_block's
from tests.unit.test_hybrid_block import (_bench, _fill, _gap, _requests,
                                          _serve)

NAME = "brumby-14b-base"

# Float32 on both sides: what separates the program's logits from the
# reference's is the order of float32 sums (the recurrence and the chunked
# form against the attention form's row blocks) through 4 layers, and the
# division by a sum of weights that a position alone can leave near 0.
# Measured here at most 4e-5 on logits of size ~3; every planted fault
# moves logits by 0.01 and more.
LOGIT_TOL = 3e-4


@pytest.fixture(scope="module")
def bench():
    return _bench()


@pytest.fixture(scope="module")
def ref(bench):
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def plants(bench):
    return bench.load_module("tools", "planted_retention").PLANTS


@pytest.fixture(scope="module")
def rc(bench):
    """The configuration file at its rehearsal size: 4 layers at width
    64, 4 query heads on 2 key/value heads of 16, float32."""
    spec = bench.benchmark_spec()
    return bench.sized(bench.config_of(spec, {"name": NAME,
                                              "config": NAME}), True)


def _cfg_of(bench, rc):
    return bench.load_module(
        "builders", "retention_lm_serving").transformer_config(rc)


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
# the pieces
# --------------------------------------------------------------------- #
def _operands(seed, B, T, n, kv, d, gate):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, T, n, d)) * d ** -0.5
    k = jax.random.normal(ks[1], (B, T, kv, d))
    v = jax.random.normal(ks[2], (B, T, kv, d))
    g = -gate * jax.random.uniform(ks[3], (B, T, kv), minval=0.5)
    return q, k, v, g


def _blank(B, kv, d):
    mixer = LinearMixerSpec.retention(kv, d)
    return (jnp.zeros((B, *mixer.state_shape), jnp.float32),
            jnp.zeros((B, *mixer.normaliser_shape), jnp.float32))


def _attention_form(q, k, v, g, eps=lm.RETENTION_EPS):
    """``a_ts = exp(G_t - G_s) (q_t . k_s)^2`` for ``s <= t``, the
    output normalised by the weights' sum: the published form, written
    out over the whole window."""
    B, T, n, d = q.shape
    group = n // k.shape[2]
    k, v = (jnp.repeat(t, group, 2) for t in (k, v))
    G = jnp.repeat(jnp.cumsum(g, 1), group, 2).transpose(0, 2, 1)
    seen = jnp.tril(jnp.ones((T, T), bool))
    w = jnp.where(seen, jnp.exp(jnp.where(
        seen, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    a = jnp.einsum("bthd,bshd->bhts", q, k) ** 2 * w
    return jnp.einsum("bhts,bshd->bthd", a, v) \
        / (a.sum(-1).transpose(0, 2, 1)[..., None] + eps)


def _stepped(q, k, v, g, state):
    """The recurrence, position by position: ``(y [B, T, ..], state)``."""
    def one(state, at):
        y, state = lm.retention_step(*at, state)
        return state, y

    state, ys = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g)))
    return jnp.moveaxis(ys, 0, 1), state


def _same(got, want, tol=2e-5):
    """To float32 rounding, by the larger side's size."""
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * float(jnp.abs(want).max()))


@pytest.mark.parametrize("d", [16, 128])
def test_the_symmetric_square_keeps_the_squared_product(d):
    q, k = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d))
    pq, pk = lm.symmetric_square(q), lm.symmetric_square(k)
    assert pq.shape == (7, d // 2 + 1, d) and pq.dtype == jnp.float32
    want = (q * k).sum(-1) ** 2
    np.testing.assert_allclose((pq * pk).sum((-1, -2)), want,
                               rtol=2e-5, atol=1e-5 * float(want.max()))
    mixer = LinearMixerSpec.retention(2, d)
    assert mixer.state_rows == (d // 2 + 1) * d
    assert mixer.state_rows_packed == d * (d + 1) // 2


# a gate near 0 keeps hundreds of positions in the state (512 positions
# at -0.01 keep e^-5 of the first); near -5 a position all but replaces
# it, and where its own weight (q . k)^2 is small its output is a ratio of
# two small float32 sums: ten times the room
@pytest.mark.parametrize("gate,T,tol", [(0.01, 512, 2e-4), (5.0, 100, 2e-3)],
                         ids=["slow", "fast"])
@pytest.mark.parametrize("chunk", [64, 48])
def test_recurrence_chunked_and_attention_forms_agree(gate, T, tol, chunk):
    """Chunks that do (64 x 8) and do not (48) divide the window."""
    B, n, kv, d = 2, 4, 2, 16
    q, k, v, g = _operands(1, B, T, n, kv, d, gate)
    want = _attention_form(q, k, v, g)
    got, after = jax.jit(lm.retention_chunked, static_argnames="chunk")(
        q, k, v, g, _blank(B, kv, d), chunk=chunk)
    assert after[0].dtype == after[1].dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)

    ys, stepped = _stepped(q, k, v, g, _blank(B, kv, d))
    np.testing.assert_allclose(ys, want, atol=tol, rtol=0)
    for a, b in zip(stepped, after):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * float(jnp.abs(b).max()))


# 5 query heads a key/value head (the published group); windows below, of
# and not a multiple of the block of the state's build
@pytest.mark.parametrize("T", [40, lm.RETENTION_CHUNK, 150],
                         ids=["below", "one-block", "ragged"])
def test_a_window_from_no_state_is_the_recurrence(T):
    """``state=None`` — no inputs yet — against the recurrence from
    zeros position by position, and against the same window handed the
    zeros: outputs, ``S`` and ``z``."""
    B, n, kv, d = 2, 10, 2, 16
    q, k, v, g = _operands(3, B, T, n, kv, d, 0.3)
    chunked = jax.jit(lm.retention_chunked)
    got, (S, z) = chunked(q, k, v, g, None)
    assert got.shape == (B, T, n, d)
    assert S.dtype == z.dtype == jnp.float32
    assert (S.shape, z.shape) == tuple(a.shape for a in _blank(B, kv, d))
    want, (want_s, want_z) = _stepped(q, k, v, g, _blank(B, kv, d))
    _same(got, want, 1e-4)
    _same(S, want_s)
    _same(z, want_z)
    zeros, (zeros_s, zeros_z) = chunked(q, k, v, g, _blank(B, kv, d))
    _same(got, zeros)
    _same(S, zeros_s)
    _same(z, zeros_z)


@pytest.mark.parametrize("T", [1, 40, 150])
def test_a_window_handed_a_state_is_still_the_recurrence(T):
    """The general term is kept: 70 positions through the recurrence,
    then the window on the state they left — the read through
    ``phi(q)``, the state decayed over the window and built on."""
    B, n, kv, d = 2, 10, 2, 16
    q, k, v, g = _operands(4, B, 70 + T, n, kv, d, 0.3)
    head, tail = (tuple(t[:, :70] for t in (q, k, v, g)),
                  tuple(t[:, 70:] for t in (q, k, v, g)))
    _, state = _stepped(*head, _blank(B, kv, d))
    assert float(jnp.abs(state[0]).max()) > 1.0          # not blank
    got, (S, z) = jax.jit(lm.retention_chunked)(*tail, state)
    want, (want_s, want_z) = _stepped(*tail, state)
    _same(got, want, 1e-4)
    _same(S, want_s)
    _same(z, want_z)


@pytest.mark.parametrize("blank", [True, False], ids=["no-state", "state"])
def test_a_padded_tail_leaves_the_state_bit_for_bit(blank):
    """``retention_attention``'s ``valid``: 90 positions of 150 are the
    prompt's, the tail's gates and keys are 0.  Whatever the tail's
    queries and values hold, ``S`` and ``z`` are the same bits, the
    prompt's outputs too — and they are the 90 positions' alone, to
    rounding.  A window that is ALL padding hands a state back as it
    came."""
    B, T, p_len, n, kv, d = 2, 150, 90, 10, 2, 16
    q, k, v, g = _operands(5, B, T, n, kv, d, 0.3)
    valid = (jnp.arange(T) < p_len)[None, :]
    k, g = k * valid[..., None, None], g * valid[..., None]
    state = None if blank else _stepped(
        *_operands(6, B, 30, n, kv, d, 0.3), _blank(B, kv, d))[1]
    chunked = jax.jit(lm.retention_chunked)
    got, after = chunked(q, k, v, g, state)
    junk = jnp.where(valid[..., None, None], 0.0, 7.0)
    other, after_junk = chunked(q + junk, k, v - junk, g, state)
    for a, b in zip(after, after_junk):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert (np.asarray(got[:, :p_len]).tobytes()
            == np.asarray(other[:, :p_len]).tobytes())
    short, after_short = chunked(*(t[:, :p_len] for t in (q, k, v, g)),
                                 state)
    _same(got[:, :p_len], short)
    for a, b in zip(after, after_short):
        _same(a, b)
    if not blank:
        _, back = chunked(q, jnp.zeros_like(k), v, jnp.zeros_like(g), state)
        for a, b in zip(back, state):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_a_padded_position_weighs_nothing_later():
    """A position inside the window whose gate and key are 0: every
    later position's numerator and normaliser are what they are without
    it — the outputs and the state of the window with the position cut
    out."""
    B, T, at, n, kv, d = 2, 50, 20, 10, 2, 16
    q, k, v, g = _operands(7, B, T, n, kv, d, 0.3)
    k, g = k.at[:, at].set(0.0), g.at[:, at].set(0.0)
    got, after = jax.jit(lm.retention_chunked)(q, k, v, g, None)
    cut = lambda t: jnp.delete(t, at, axis=1)
    want, want_after = jax.jit(lm.retention_chunked)(
        *map(cut, (q, k, v, g)), None)
    _same(cut(got), want)
    for a, b in zip(after, want_after):
        _same(a, b)


def test_a_slow_gate_needs_a_float32_state():
    """512 positions at a gate near 0: a state rounded to bf16 after
    every position is off by 25 times and more the tolerance the float32
    one meets (what the chip's comparison cannot see: the benchmark's weights
    give a gate near -0.7)."""
    B, T, n, kv, d = 1, 512, 4, 2, 16
    q, k, v, g = _operands(2, B, T, n, kv, d, 0.01)
    want = _attention_form(q, k, v, g)
    narrow = lambda a: jax.lax.reduce_precision(a, 8, 7)

    def one(state, at):
        y, state = lm.retention_step(*at, state)
        return tuple(map(narrow, state)), y

    _, ys = jax.lax.scan(one, _blank(B, kv, d), tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g)))
    assert float(jnp.abs(jnp.moveaxis(ys, 0, 1) - want).max()) > 5e-3


# --------------------------------------------------------------------- #
# the state kernel, under the interpreter
# --------------------------------------------------------------------- #
def test_the_kernel_is_the_composed_step_in_place():
    """Heads of 128 (the kernel's tiles): the kernel's output and the
    layer's state against the composed step's; the other layer's tiles,
    and those of a slot whose gate is 0 and whose key writes nothing, bit
    for bit."""
    from autodist_tpu.kernel.pallas import retention_step as rs

    L, B, kv, group, d, layer = 2, 2, 1, 2, 128, 1
    mixer = LinearMixerSpec.retention(kv, d)
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    q = jax.random.normal(ks[0], (B, kv * group, d)) * d ** -0.5
    k, v = (jax.random.normal(key, (B, kv, d)) for key in ks[1:3])
    # the second slot neither decays nor writes: its tiles stay
    g = -jax.random.uniform(ks[3], (B, kv)) * jnp.array([[1.0], [0.0]])
    k = k * jnp.array([1.0, 0.0])[:, None, None]
    ssm = jax.random.normal(ks[4], (L, B, *mixer.state_shape))
    nrm = jnp.abs(jax.random.normal(ks[5], (L, B, *mixer.normaliser_shape)))
    assert rs.retention_step_fits(ssm.shape, ssm.dtype, group)
    assert not rs.retention_step_fits(ssm.shape, jnp.bfloat16, group)
    assert not rs.retention_step_fits(ssm.shape, ssm.dtype, 7)
    want_y, (want_s, want_z) = lm.retention_step(
        q, k, v, g, (ssm[layer], nrm[layer]))
    y, (s, z) = rs.retention_step_fused(
        q, k, v, g, (ssm, nrm), layer, eps=lm.RETENTION_EPS,
        offsets_per_step=5, interpret=True)
    assert s.dtype == z.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, rtol=0,
                               atol=1e-4 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(s[layer], want_s, atol=1e-5, rtol=0)
    np.testing.assert_allclose(z[layer], want_z, atol=1e-5, rtol=0)
    assert bool((s[0] == ssm[0]).all() and (z[0] == nrm[0]).all())
    assert bool((s[layer, 1] == ssm[layer, 1]).all()
                and (z[layer, 1] == nrm[layer, 1]).all())
    with pytest.raises(ValueError, match="float32 state"):
        rs.retention_step_fused(q, k, v, g, (ssm.astype(jnp.bfloat16), nrm),
                                layer, eps=1e-6, interpret=True)


@pytest.mark.parametrize("backend,word,want", [
    ("tpu", None, True), ("cpu", None, False), ("cpu", True, True),
    ("tpu", False, False)])
def test_the_election_reads_what_the_call_observes(backend, word, want):
    from autodist_tpu.kernel.pallas import (KERNEL_CHOICES, OBSERVED_KERNELS,
                                            kernel_marker)
    from autodist_tpu.kernel.pallas.retention_step import \
        retention_step_elected

    shape = (8, 16, 8, 65, 128, 128)
    assert retention_step_elected(word, shape, jnp.float32, 5,
                                  backend=backend) == want
    # heads of 16 (the rehearsal's), or a state that is not float32
    assert not retention_step_elected(True, (4, 4, 2, 9, 16, 16),
                                      jnp.float32, 2, backend="tpu")
    assert not retention_step_elected(True, shape, jnp.bfloat16, 5)
    assert "retention_step" in KERNEL_CHOICES
    assert "retention_step" in OBSERVED_KERNELS
    assert kernel_marker("retention_step") == "adtk_retention_step"


# --------------------------------------------------------------------- #
# the whole model against the plain reference
# --------------------------------------------------------------------- #
def test_the_block_is_the_published_one(cfg):
    spec = cfg.block
    assert spec.layer_kinds(4) == ("linear",) * 4
    assert spec.linear == LinearMixerSpec.retention(2, 16)
    assert (spec.linear.rule, spec.linear.power) == ("retention", 2)
    assert not spec.linear.has_conv and spec.linear.has_normaliser
    assert spec.qk_norm and spec.positions == "rope"
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 16)
    # the delta rule's specs are what they were
    delta = LinearMixerSpec(16, 32, 128, 128)
    assert (delta.rule, delta.state_shape, delta.state_heads,
            delta.state_rows, delta.has_conv, delta.has_normaliser) == (
        "delta", (32, 128, 128), 32, 128, True, False)
    assert delta == LinearMixerSpec(16, 32, 128, 128, conv_taps=4,
                                    gate="head", gate_floor=0.0)


@pytest.mark.parametrize("change,message", [
    (dict(kv_heads=4), "power retention reads the block's own"),
    (dict(head_dim=32), "power retention reads the block's own"),
    (dict(attn_gate=True), "power retention reads the block's own"),
    (dict(layer_period=("linear", "full")),
     "power retention reads the block's own"),
    (dict(linear=LinearMixerSpec(2, 2, 16, 16)),
     "qk_norm, kv_heads and head_dim are attention's"),
])
def test_block_spec_refuses_what_it_cannot_mean(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(cfg.block, **change)


@pytest.mark.parametrize("change", [
    dict(power=3), dict(conv_taps=4), dict(gate="channel", gate_floor=-5.0),
    dict(value_heads=4), dict(value_dim=32), dict(key_dim=15, value_dim=15)])
def test_retention_spec_refuses(change):
    kw = dict(key_heads=2, value_heads=2, key_dim=16, value_dim=16,
              conv_taps=0, rule="retention", power=2)
    kw.update(change)
    with pytest.raises(ValueError):
        LinearMixerSpec(**kw)
    with pytest.raises(ValueError, match="one of"):
        LinearMixerSpec(2, 2, 16, 16, rule="mamba")
    with pytest.raises(ValueError, match="retention's degree"):
        LinearMixerSpec(2, 2, 16, 16, power=2)


@pytest.mark.parametrize("length", [1, 150])
def test_sequential_logits_match_the_reference(ref, rc, cfg, params, length):
    """150 positions from no state: the attention form over the window,
    the state built in three blocks; one position from no state is a
    window like another (one position ON a state is the engine's decode
    step, below)."""
    wide = dataclasses.replace(cfg, max_len=256)
    tokens = jax.random.randint(jax.random.PRNGKey(length), (2, length), 0,
                                cfg.vocab_size)
    got = lm.sequential_logits(wide, params, tokens)
    want = ref.forward(params, tokens, rc)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_prefill_then_decode_through_the_cache(ref, rc, cfg, params):
    """Ragged admissions on three slots, every slot reused after an
    eviction: each served token is the reference's first choice at its
    position, over the whole of every request; the manager says it holds
    no keys and values, and what it holds instead."""
    telemetry.reset()
    requests = _requests()
    served = _serve(cfg, params, requests)
    assert [len(t) for _, t in served] == [o for _, o in requests]
    assert _gap(ref, rc, params, served) <= LOGIT_TOL
    counts = {m["name"]: m["value"]
              for m in telemetry.get().registry.snapshot() if "value" in m}
    assert counts["engine/cache_layers"] == 0
    assert counts["engine/kv_bytes_per_token"] == 0
    # 4 layers x (2 heads x 9 offsets x 16 x 16 + 9 x 2 x 16) float32
    per_slot = 4 * (2 * 9 * 16 * 16 + 9 * 2 * 16) * 4
    assert counts["engine/state_bytes_per_slot"] == per_slot
    assert counts["kv/state_bytes"] == 3 * per_slot
    assert counts["kv/state_rows"] == 9 * 16
    assert counts["kernel/retention_step_elected"] == 0     # the CPU
    assert "kernel/delta_step_elected" not in counts
    assert "serve/kv_blocks_resident" not in counts
    assert counts["engine/state_rows"] > 0
    assert counts["engine/state_prompts"] == 4 * len(requests)
    # every one of them from no state
    assert counts["engine/state_prompts_blank"] == 4 * len(requests)


def test_the_state_is_float32_and_no_keys_are_held(cfg, params):
    engine = ServingEngine(cfg, params, num_slots=3, max_len=48,
                           prefill_len=16, decode_steps=4)
    state = engine.cache.state
    assert state.conv is None
    assert state.ssm.shape == (4, 3, 2, 9, 16, 16)
    assert state.norm.shape == (4, 3, 9, 2, 16)
    assert state.ssm.dtype == state.norm.dtype == jnp.float32
    assert engine.cache.k.size == engine.cache.v.size == 0
    assert engine.cache_layers == 0 and engine.decode_block_len is None
    assert engine.kv.fused_block is None
    assert not engine.kernel.get("flash_decode")
    assert kv_cache.bytes_held((0, 3, 2, 16, 48), jnp.float32,
                               (4, cfg.block.linear)) == {
        "kv_bytes_per_token": 0,
        "state_bytes_per_slot": 4 * (2 * 9 * 16 * 16 + 9 * 2 * 16) * 4}


def test_the_gate_is_float32_in_a_bf16_block(cfg, params):
    """bf16 activations: the gate's projection, its log sigmoid and the
    state stay float32 (the step is handed them so)."""
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    chunk = lm.layer_chunk(half, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), params["stages"]), 0)
    seen = {}

    def step(q, k, v, g, state):
        seen.update(g=g.dtype, q=q.dtype, state=[a.dtype for a in state])
        return lm.retention_step(q, k, v, g, state)

    x = jnp.ones((2, 1, half.hidden_size), jnp.bfloat16)
    lm.retention_attention(half, chunk, x, lm.blank_linear_state(half, 2),
                           jnp.zeros((2, 1), jnp.int32), step=step)
    assert seen == {"g": jnp.float32, "q": jnp.float32,
                    "state": [jnp.float32, jnp.float32]}


@pytest.mark.parametrize("plant", ["gate_after_write", "wrong_group",
                                   "no_rotary", "stale_state"])
def test_a_planted_fault_reads_far_above_a_sound_run(ref, rc, bench, params,
                                                     plants, plant):
    """Each fault of ``benchmark/tools/planted_retention.py`` under the
    engine: a gate that decays its own write, a query head reading the
    wrong key/value head, rotary left off, a state not overwritten at
    admission."""
    with plants[plant]():
        served = _serve(_cfg_of(bench, rc), params, _requests())
    assert _gap(ref, rc, params, served) > 30 * LOGIT_TOL


def test_a_bf16_state_fails_at_a_slow_gate(ref, rc, bench, params, plants,
                                           monkeypatch):
    """The chip's comparison cannot see a bf16 state: the benchmark's
    weights give ``gamma`` ~ -0.7 and the state forgets within a few
    positions.  Here the gate is slowed in program and reference alike
    (``gamma`` ~ -0.02: ``log sigmoid`` of the projection plus 4), where a
    sound run still meets the tolerance and the state rounded to bf16
    after every step does not (it moves logits by a few thousandths:
    the served tokens it flips lie up to that far below the reference's
    best)."""
    shift = lambda real: (lambda x: real(x + 4.0))
    monkeypatch.setattr(jax.nn, "log_sigmoid", shift(jax.nn.log_sigmoid))
    ref_slow = bench.load_module("reference", NAME)     # its own caches
    requests = [(p, 12) for p, _ in _requests(4)] + [
        (np.arange(40, dtype=np.int32) % 509, 8)]
    kw = dict(max_len=64, prefill_len=48)
    served = _serve(_cfg_of(bench, rc), params, requests, **kw)
    assert _gap(ref_slow, rc, params, served) <= LOGIT_TOL
    with plants["state_bf16"]():
        served = _serve(_cfg_of(bench, rc), params, requests, **kw)
    assert _gap(ref_slow, rc, params, served) > 2 * LOGIT_TOL


def test_an_evicted_slots_state_is_overwritten_whole(cfg, params):
    """A slot that held a long request, then a short one: the state the
    second prefill leaves is the one it leaves in a fresh engine, bit for
    bit — nothing of the previous occupant is read."""
    def admit(engine, prompt):
        prompts = np.zeros((2, 16), np.int32)
        prompts[1, :len(prompt)] = prompt
        engine.prefill(prompts, np.array([0, len(prompt)]),
                       np.array([False, True]))
        return [np.asarray(a)[:, 1] for a in engine._state_args()]

    make = lambda: ServingEngine(cfg, params, num_slots=2, max_len=48,
                                 prefill_len=16, decode_steps=4)
    r = np.random.default_rng(0)
    long, short = r.integers(0, 509, 16), r.integers(0, 509, 3)
    used = make()
    admit(used, long)
    used.decode(np.array([False, True]))
    for a, b in zip(admit(used, short), admit(make(), short)):
        assert (a == b).all()


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
    (dict(tensor_parallel=2), "power retention's key/value heads"),
])
def test_engine_refuses_by_name(cfg, params, kw, message):
    with pytest.raises(ValueError, match=message) as e:
        ServingEngine(cfg, params, num_slots=2, max_len=48, prefill_len=16,
                      **kw)
    if "recurrent state" in message:
        assert "power-retention" in str(e.value)
        assert "DeltaNet" not in str(e.value)


def test_the_builder_refuses_what_the_block_does_not_implement(bench, rc):
    builder = bench.load_module("builders", "retention_lm_serving")
    for change, says in [
            ({"use_sliding_window": True}, "use_sliding_window"),
            ({"sliding_window": 4096}, "a sliding_window"),
            ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
            ({"attention_bias": True}, "attention_bias"),
            ({"tie_word_embeddings": True}, "tie_word_embeddings"),
            ({"max_window_layers": 28}, "max_window_layers")]:
        with pytest.raises(ValueError, match=says):
            builder.transformer_config(dict(rc, **change))


# --------------------------------------------------------------------- #
# what the existing readers find: the scopes, the gauges, the counter
# --------------------------------------------------------------------- #
def test_the_mixer_wears_the_scopes_the_readers_look_for(cfg, params):
    """The projections and the gate wear ``linear_attention``, the state
    pass ``state_update`` inside it — in a decode step (through the cache
    manager's seam) and in a prompt's chunked pass — and the norm and the
    rotation their own."""
    import re

    chunk = lm.layer_chunk(cfg, params["stages"], 0)
    layout = kv_cache.DenseLayout((0, 2, 2, 16, 8), {},
                                  recurrent=(4, cfg.block.linear))
    state = kv_cache.init_state(4, 2, cfg.block.linear, jnp.float32).arrays()

    def decode(x, state):
        return lm.retention_attention(
            cfg, chunk, x, state, jnp.zeros((2, 1), jnp.int32),
            step=lambda *a: layout.advance_retention(*a, 1))

    def prompt(x):
        return lm.mix_linear(cfg, chunk, x, lm.blank_linear_state(cfg, 1),
                             jnp.arange(70))

    for fn, args in ((decode, (jnp.ones((2, 1, 64)), state)),
                     (prompt, (jnp.ones((1, 70, 64)),))):
        names = set(re.findall(r'loc\("([^"]*)"', jax.jit(fn).lower(*args)
                               .as_text(debug_info=True)))
        assert any("linear_attention/state_update/" in n for n in names)
        assert any(n.endswith("linear_attention/dot_general")
                   for n in names)
        assert any("/rope/" in n and "linear_attention" not in n
                   for n in names)
        assert not any("/attention/" in n for n in names)


def test_a_prompts_program_expands_no_query(cfg, params):
    """The engine's lowered prefill program multiplies no ``[.., offsets,
    d]`` expansion of a QUERY (a row of ``phi`` a query head and
    position: the read of a state, which a prompt's pass is not handed);
    the keys' expansion, a key/value head's, is what builds the state.
    The same search finds the read where a state does come in."""
    import re

    engine = ServingEngine(cfg, params, num_slots=2, max_len=48,
                           prefill_len=16, decode_steps=4)
    c = engine.cache
    prompt = engine._prefill_jit.lower(
        engine.params, c.k, c.v, c.lengths, engine._tok,
        *engine._blank_prefill_args()).as_text()
    kv, group, d = cfg.kv_heads, cfg.num_heads // cfg.kv_heads, cfg.head_dim
    dots = lambda text: [line for line in text.splitlines()
                         if "stablehlo.dot_general" in line]
    of_a_query = re.compile(rf"tensor<1x{kv}x{group}x\d+x{d // 2 + 1}x{d}xf32>")
    of_a_key = re.compile(rf"tensor<1x{kv}x\d+x{d // 2 + 1}x{d}xf32>")
    assert not [line for line in dots(prompt) if of_a_query.search(line)]
    assert len([line for line in dots(prompt)
                if of_a_key.search(line)]) == 1      # traced once a kind
    q, k, v, g = _operands(8, 1, 16, cfg.num_heads, kv, d, 0.3)
    carried = jax.jit(lm.retention_chunked).lower(
        q, k, v, g, _blank(1, kv, d)).as_text()
    assert len([line for line in dots(carried)
                if of_a_query.search(line)]) == 2    # S and z


def _report_tool():
    import importlib
    import os
    import sys

    from tests.unit.test_hybrid_block import ROOT

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        return importlib.import_module("telemetry_report")
    finally:
        sys.path.pop(0)


def test_report_check_knows_the_gauges_and_the_counter(tmp_path):
    import json
    import os

    report = _report_tool()
    gauge = lambda name, v: {"kind": "gauge", "name": name, "value": v}
    count = lambda name, v: {"kind": "counter", "name": name, "value": v}
    sound = [gauge("engine/state_bytes_per_slot", 274763776),
             gauge("kv/state_bytes", 16 * 274763776),
             gauge("kv/state_rows", 8320),
             gauge("kernel/retention_step_elected", 1),
             count("engine/prefill_rows", 7),
             count("engine/prefill_positions", 7 * 256),
             count("engine/prefill_rung_rows/256", 7),
             count("engine/state_prompts", 56)]

    def problems(records):
        with open(os.path.join(tmp_path, "metrics.jsonl"), "w") as f:
            f.write("\n".join(json.dumps(r) for r in records) + "\n")
        return report.check_schema(str(tmp_path))

    assert problems(sound) == []
    assert any("come together and positive" in p
               for p in problems(sound[:1] + sound[2:]))
    assert any("holds a recurrent state" in p for p in problems(sound[3:]))
    assert any("1 (the fused kernel) or 0" in p for p in problems(
        sound[:3] + [gauge("kernel/retention_step_elected", 2)]))
    assert any("whole layers of the rows" in p
               for p in problems(sound[:-1]
                                 + [count("engine/state_prompts", 57)]))


@pytest.mark.parametrize("records,says", [
    ([("engine/state_prompts", 56), ("engine/state_prompts_blank", 56)],
     None),
    # the day a state is handed across a chunk's edge
    ([("engine/state_prompts", 56), ("engine/state_prompts_blank", 40)],
     None),
    ([("engine/state_prompts", 56), ("engine/state_prompts_blank", 57)],
     "some of the states built"),
    ([("engine/state_prompts_blank", 56)], "some of the states built"),
], ids=["equal", "fewer", "more", "alone"])
def test_report_check_holds_the_blank_count_under_the_states_built(
        tmp_path, records, says):
    import json
    import os

    report = _report_tool()
    rows = [{"kind": "counter", "name": "engine/prefill_rows", "value": 7},
            {"kind": "counter", "name": "engine/prefill_positions",
             "value": 7 * 256},
            {"kind": "counter", "name": "engine/prefill_rung_rows/256",
             "value": 7}] + [
        {"kind": "counter", "name": n, "value": v} for n, v in records]
    with open(os.path.join(tmp_path, "metrics.jsonl"), "w") as f:
        f.write("\n".join(json.dumps(r) for r in rows) + "\n")
    problems = report.check_schema(str(tmp_path))
    if says is None:
        assert problems == []
    else:
        assert any(says in p for p in problems)
