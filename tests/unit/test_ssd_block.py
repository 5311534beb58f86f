"""A stack that mixes Mamba-2 state-space layers — heads that share one B
and one C a group, a scalar decay a head, a biased convolution over ``[x |
B | C]``, a skip and a gate before the norm — with grouped-query attention
that has no positions, under an embedding, a residual, a softmax and a
logits multiplier.

The pieces on their own (one position against the equations written out
in numpy; the chunked form against position by position; slow heads,
where a bf16 state shows; padding; the state kernel under the interpreter
against the composed step; a softmax scale that is no power of two through
every path that attends), then the program — ``sequential_logits``, and
the engine's prefill then fused decode through the cache manager —
against the benchmark's plain reference
(``benchmark/reference/granite-4.0-h-micro.py``, which shares no code with
the program and runs the recurrence position by position) at the
rehearsal's size with seeded weights in float32; the planted faults; and
what such a block refuses, each by name.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.kernel.pallas import ssd_step as ss
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.models.transformer import (BlockSpec, LinearMixerSpec,
                                             TransformerConfig)
from autodist_tpu.serving import ServingEngine, kv_cache

# the loader, seeded weights (a ``scale`` about 1, everything else about
# 0), ragged requests, the engine under a batcher and the widest gap to
# the reference: test_hybrid_block's
from tests.unit.test_hybrid_block import (_bench, _fill, _gap, _requests,
                                          _serve)

NAME = "granite-4.0-h-micro"
BUILDER = "hybrid_ssd_lm_serving"

# Float32 on both sides: what separates the program's logits from the
# reference's is the order of float32 sums (the chunked form against the
# recurrence) through 10 layers.  Measured here at most 2e-8 on logits of
# size ~0.05 (the logits are divided by 8); every planted fault moves the
# served tokens' gap by 4e-4 and more.
LOGIT_TOL = 1e-5


@pytest.fixture(scope="module")
def bench():
    return _bench()


@pytest.fixture(scope="module")
def ref(bench):
    return bench.load_module("reference", NAME)


@pytest.fixture(scope="module")
def plants(bench):
    return bench.load_module("tools", "planted_ssd").PLANTS


@pytest.fixture(scope="module")
def rc(bench):
    """The configuration file at its rehearsal size: one period of 10
    layers at width 64, 4 state-space heads of 16 over a state of 16,
    4 query heads on 2 key/value heads of 16, float32."""
    spec = bench.benchmark_spec()
    return bench.sized(bench.config_of(spec, {"name": NAME,
                                              "config": NAME}), True)


def _cfg_of(bench, rc):
    return bench.load_module("builders", BUILDER).transformer_config(rc)


def _short(rc, **changes):
    """The rehearsal cut to three layers — a state-space layer, an
    attention layer, a state-space layer — where a test needs every kind
    and not the depth."""
    return dict(rc, num_hidden_layers=3,
                layer_types=["mamba", "attention", "mamba"], **changes)


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
def _operands(seed, B, T, heads, P, G, N, rate=1.0):
    """``x, Bm, Cm, g, dt`` of a window; ``rate`` scales the log decay
    (``Delta A``) a position."""
    r = np.random.default_rng(seed)
    f = lambda *shape: r.normal(size=shape).astype(np.float32)
    dt = np.abs(f(B, T, heads)) + 0.1
    g = -dt * rate * (0.5 + r.random(heads).astype(np.float32))
    return f(B, T, heads, P), f(B, T, G, N), f(B, T, G, N), g, dt


def _written_out(x, Bm, Cm, g, dt, S0=None):
    """The equations in numpy, head by head as the model states them:
    ``S[h] <- exp(g[h]) S[h] + (dt[h] x[h]) (x) B``, ``y[h] = S[h] C``
    with ``S[h]`` ``[P, N]``.  Returns ``(y [B, T, heads, P], S [B,
    heads, P, N])``."""
    B, T, heads, P = x.shape
    G, N = Bm.shape[2:]
    S = np.zeros((B, heads, P, N), np.float64) if S0 is None \
        else S0.astype(np.float64)
    y = np.zeros((B, T, heads, P), np.float64)
    for t in range(T):
        for h in range(heads):
            grp = h // (heads // G)
            S[:, h] = np.exp(g[:, t, h])[:, None, None] * S[:, h] \
                + (dt[:, t, h, None] * x[:, t, h])[:, :, None] \
                * Bm[:, t, grp][:, None, :]
            y[:, t, h] = (S[:, h] * Cm[:, t, grp][:, None, :]).sum(-1)
    return y, S


def _as_held(S, G):
    """``[B, heads, P, N]`` as the program keeps it: ``[B, G, N, heads a
    group * P]``."""
    B, heads, P, N = S.shape
    return np.transpose(S.reshape(B, G, heads // G, P, N),
                        (0, 1, 4, 2, 3)).reshape(B, G, N, -1)


@pytest.mark.parametrize("G", [1, 2])
def test_one_position_is_the_equations(G):
    B, heads, P, N = 2, 4, 8, 16
    x, Bm, Cm, g, dt = _operands(0, B, 3, heads, P, G, N)
    S0 = np.random.default_rng(1).normal(
        size=(B, heads, P, N)).astype(np.float32)
    want_y, want_S = _written_out(x, Bm, Cm, g, dt, S0)
    S = jnp.asarray(_as_held(S0, G))
    assert S.shape[1:] == LinearMixerSpec.ssd(heads, P, N, G).state_shape
    for t in range(3):
        y, S = lm.ssd_step(x[:, t], Bm[:, t], Cm[:, t], g[:, t], dt[:, t],
                           S)
        np.testing.assert_allclose(y, want_y[:, t], atol=2e-5, rtol=1e-5)
    assert S.dtype == jnp.float32
    np.testing.assert_allclose(S, _as_held(want_S, G), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("T,chunk,from_state", [
    (20, 32, False), (32, 32, True), (70, 32, True), (96, 32, False),
    (300, lm.SSD_CHUNK, True)])
def test_the_chunked_form_is_the_recurrence(T, chunk, from_state):
    """A window below, of and not a multiple of the chunk, from no state
    (an admitted prompt) and on a state that came in, two groups."""
    B, heads, P, G, N = 2, 4, 8, 2, 16
    x, Bm, Cm, g, dt = _operands(T, B, T, heads, P, G, N)
    S0 = np.random.default_rng(2).normal(
        size=(B, heads, P, N)).astype(np.float32) if from_state else None
    want_y, want_S = _written_out(x, Bm, Cm, g, dt, S0)
    y, S = lm.ssd_chunked(
        *map(jnp.asarray, (x, Bm, Cm, g, dt)),
        None if S0 is None else jnp.asarray(_as_held(S0, G)), chunk=chunk)
    assert S.dtype == jnp.float32
    scale = float(np.abs(want_y).max())
    np.testing.assert_allclose(y, want_y, atol=2e-5 * scale, rtol=0)
    np.testing.assert_allclose(S, _as_held(want_S, G),
                               atol=2e-5 * float(np.abs(want_S).max()),
                               rtol=0)


def test_a_bf16_state_fails_at_slow_heads():
    """``Delta A`` ~ -0.01 a position over 512 positions: the state is a
    sum of hundreds of writes, float32 holds it to 1e-5 of its size and a
    state rounded to bf16 after every position is off by a hundred times
    that."""
    B, T, heads, P, G, N = 1, 512, 4, 8, 1, 16
    x, Bm, Cm, g, dt = _operands(7, B, T, heads, P, G, N, rate=0.01)
    assert -0.03 < float(g.mean()) < -0.003
    want_y, want_S = _written_out(x, Bm, Cm, g, dt)
    size = float(np.abs(want_y).max())
    narrow = lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                                mantissa_bits=7)

    def run(after):
        S, ys = jnp.zeros((B, G, N, heads * P), jnp.float32), []
        for t in range(T):
            y, S = lm.ssd_step(x[:, t], Bm[:, t], Cm[:, t], g[:, t],
                               dt[:, t], S)
            S = after(S)
            ys.append(y)
        return np.stack(ys, 1)

    tol = 2e-5 * size
    assert np.abs(run(lambda S: S) - want_y).max() < tol
    assert np.abs(run(narrow) - want_y).max() > 50 * tol
    y, _ = lm.ssd_chunked(*map(jnp.asarray, (x, Bm, Cm, g, dt)), None)
    assert np.abs(np.asarray(y) - want_y).max() < tol


def _mixer_chunk(cfg, params, l=0):
    return lm.layer_chunk(cfg, params["stages"], l)


def test_padding_moves_neither_state_nor_tail(cfg, params):
    """A window of 12 positions whose position 5 and last three are
    padding (``valid`` false, ``length`` 9 counted up to the tail's cut):
    whatever the padded positions hold, the state and the tail come out
    bit for bit the same — and equal to the window without them."""
    chunk = _mixer_chunk(cfg, params)
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(2, 12, cfg.hidden_size)), jnp.float32)
    valid = np.ones((2, 12), bool)
    valid[:, 9:] = False
    valid = jnp.asarray(valid)
    length = jnp.array([9, 9])
    run = lambda x, valid=valid, length=length: lm.mix_linear(
        cfg, chunk, x, None, jnp.arange(x.shape[1]), valid=valid,
        length=length)
    y, (tail, S) = run(x)
    other = x.at[:, 9:].set(x[:, 9:] * -3.0 + 1.0)
    y2, (tail2, S2) = run(other)
    assert bool((S == S2).all() and (tail == tail2).all())
    assert bool((y[:, :9] == y2[:, :9]).all())
    assert S.dtype == jnp.float32
    # the window cut at 9: the same state and tail
    y3, (tail3, S3) = run(x[:, :9], None, None)
    np.testing.assert_allclose(S, S3, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(tail, tail3, atol=0, rtol=0)
    np.testing.assert_allclose(y[:, :9], y3, atol=1e-6, rtol=1e-5)
    # a padded position INSIDE a window of the recurrence (no decay, no
    # write): whatever x, B and C it holds, the state and every other
    # position's output pass over it bit for bit (the convolution before
    # it does see a window's inputs: the engine pads a row's end alone)
    ops = [jnp.asarray(t) for t in _operands(11, 2, 12, 4, 8, 2, 16)]
    ops[3], ops[4] = ops[3].at[:, 5].set(0.0), ops[4].at[:, 5].set(0.0)
    S0 = jnp.asarray(r.normal(size=(2, 2, 16, 16)), jnp.float32)
    y4, S4 = lm.ssd_chunked(*ops, S0)
    for i in range(3):
        ops[i] = ops[i].at[:, 5].set(ops[i][:, 5] * 5.0 - 2.0)
    y5, S5 = lm.ssd_chunked(*ops, S0)
    keep = np.arange(12) != 5
    assert bool((S4 == S5).all() and (y4[:, keep] == y5[:, keep]).all())
    # one position on a state, padded: the state comes back bit for bit
    S0 = jnp.asarray(r.normal(size=S.shape), jnp.float32)
    _, (_, S6) = lm.ssd_attention(cfg, chunk, x[:, :1], (tail, S0),
                                  valid=jnp.zeros((2, 1), bool))
    assert bool((S6 == S0).all())


def test_the_step_and_the_state_are_float32_in_a_bf16_block(cfg, params):
    """bf16 activations: ``Delta``, the log decay and the state stay
    float32 (the step is handed them so)."""
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    chunk = lm.layer_chunk(half, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), params["stages"]), 0)
    seen = {}

    def step(x, Bm, Cm, g, dt, state):
        seen.update(g=g.dtype, dt=dt.dtype, x=x.dtype, B=Bm.dtype,
                    state=state.dtype)
        return lm.ssd_step(x, Bm, Cm, g, dt, state)

    x = jnp.ones((2, 1, half.hidden_size), jnp.bfloat16)
    tail, S = lm.blank_linear_state(half, 2)
    assert tail.dtype == jnp.bfloat16 and S.dtype == jnp.float32
    lm.ssd_attention(half, chunk, x, (tail, S), step=step)
    assert set(seen.values()) == {jnp.dtype(jnp.float32)}


@pytest.mark.parametrize("G", [1, 2])
def test_kernel_matches_the_composed_step_under_the_interpreter(G):
    """``[N, width]`` matrices of whole lanes, the second slot neither
    decaying nor written: the layer's matrices advance as the composed
    step's, every other layer's stay bit for bit, and a bf16 state is
    refused, never cast."""
    L, B, heads, P, N, layer = 3, 2, 16, 16, 16, 1
    mixer = LinearMixerSpec.ssd(heads, P, N, G)
    x, Bm, Cm, g, dt = (jnp.asarray(t[:, 0]) for t in _operands(
        5, B, 1, heads, P, G, N))
    still = jnp.array([1.0, 0.0])[:, None]
    g, dt = g * still, dt * still
    ssm = jax.random.normal(jax.random.PRNGKey(5), (L, B, *mixer.state_shape))
    assert ss.ssd_step_fits(ssm.shape, ssm.dtype)
    assert not ss.ssd_step_fits(ssm.shape, jnp.bfloat16)
    assert not ss.ssd_step_fits((L, B, 1, 16, 64), jnp.float32)
    want_y, want_s = lm.ssd_step(x, Bm, Cm, g, dt, ssm[layer])
    y, s = ss.ssd_step_fused(x, Bm, Cm, g, dt, ssm, layer, interpret=True)
    assert s.dtype == jnp.float32 and s.shape == ssm.shape
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s[layer], want_s, atol=1e-6, rtol=1e-6)
    assert bool((s[0] == ssm[0]).all() and (s[2] == ssm[2]).all())
    assert bool((s[layer, 1] == ssm[layer, 1]).all())
    with pytest.raises(ValueError, match="float32 state"):
        ss.ssd_step_fused(x, Bm, Cm, g, dt, ssm.astype(jnp.bfloat16), layer,
                          interpret=True)


@pytest.mark.parametrize("backend,word,want", [
    ("tpu", None, True), ("cpu", None, False), ("cpu", True, True),
    ("tpu", False, False)])
def test_the_election_reads_what_the_call_observes(backend, word, want):
    from autodist_tpu.kernel.pallas import (KERNEL_CHOICES, OBSERVED_KERNELS,
                                            kernel_marker)

    shape = (36, 64, 1, 128, 4096)      # the benchmark's cell
    assert ss.ssd_step_elected(word, shape, jnp.float32,
                               backend=backend) == want
    # the rehearsal's width (64: half a lane tile), or a bf16 state
    assert not ss.ssd_step_elected(True, (9, 4, 1, 16, 64), jnp.float32,
                                   backend="tpu")
    assert not ss.ssd_step_elected(True, shape, jnp.bfloat16)
    assert "ssd_step" in KERNEL_CHOICES and "ssd_step" in OBSERVED_KERNELS
    assert kernel_marker("ssd_step") == "adtk_ssd_step"


def test_a_scale_that_is_no_power_of_two_reaches_every_path():
    """0.3 where ``head_dim ** -0.5`` is 0.25: the composed decode and
    window attention, the dense and the paged decode kernels and the
    paged prefill kernel under the interpreter, and the einsum attention
    a prompt takes, each against scores scaled in numpy."""
    from autodist_tpu.kernel.pallas.flash_decode import (
        flash_decode_attention_dense, flash_decode_attention_paged)
    from autodist_tpu.kernel.pallas.flash_prefill import \
        flash_prefill_attention_paged
    from autodist_tpu.models.transformer import dot_product_attention

    B, n, kv, T, d, scale = 2, 4, 2, 32, 16, 0.3
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, 1, n, d))
    kc = jax.random.normal(ks[1], (1, B, kv, T, d))
    vc = jax.random.normal(ks[2], (1, B, kv, T, d))
    lengths = jnp.array([20, 7])

    def plain(q, k, v, upto):       # [n, d] against [kv, T, d], in numpy
        k, v = (np.repeat(np.asarray(t), n // kv, 0) for t in (k, v))
        s = np.einsum("nd,ntd->nt", np.asarray(q), k) * scale
        s[:, upto + 1:] = -np.inf
        p = np.exp(s - s.max(-1, keepdims=True))
        return np.einsum("nt,ntd->nd", p / p.sum(-1, keepdims=True), v)

    want = np.stack([plain(q[b, 0], kc[0, b], vc[0, b], int(lengths[b]))
                     for b in range(B)])[:, None]
    got = kv_cache.cached_attention(q, kc[0], vc[0], lengths, scale=scale)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and without the scale it is another answer
    off = kv_cache.cached_attention(q, kc[0], vc[0], lengths)
    assert float(jnp.abs(off - want).max()) > 1e-3
    fused = flash_decode_attention_dense(q, kc, vc, 0, lengths, block_k=16,
                                         interpret=True, scale=scale)
    np.testing.assert_allclose(fused, want, atol=1e-5)
    # a window of 3 rows from each slot's length (full heads: the window
    # readers take a key/value head a query head)
    k4, v4 = (jnp.repeat(t, n // kv, 2) for t in (kc, vc))
    qw = jax.random.normal(ks[3], (B, 3, n, d))
    want_w = np.stack([[plain(qw[b, c], kc[0, b], vc[0, b],
                              int(lengths[b]) + c) for c in range(3)]
                       for b in range(B)])
    got_w = kv_cache.chunk_attention(qw, k4[0], v4[0], lengths, scale=scale)
    np.testing.assert_allclose(got_w, want_w, atol=1e-5)
    # the paged pool: slot b's lane is blocks 4 b .. 4 b + 3 of 8
    pool = lambda c: c[0].reshape(B, n, 4, 8, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B * 4, n, 8, d)
    table = jnp.arange(8, dtype=jnp.int32).reshape(B, 4)
    for attend, rows, want_rows, at in (
            (kv_cache.paged_cached_attention, q, want, lengths),
            (flash_decode_attention_paged, q, want, lengths),
            (kv_cache.paged_chunk_attention, qw, want_w, lengths),
            (flash_prefill_attention_paged, qw, want_w, lengths)):
        kw = dict(interpret=True) if attend.__name__.startswith("flash") \
            else {}
        got_p = attend(rows, pool(k4), pool(v4), at, table, block_len=8,
                       scale=scale, **kw)
        np.testing.assert_allclose(got_p, want_rows, atol=1e-5,
                                   err_msg=attend.__name__)
    # a prompt's pass: causal over the first 8 positions
    qp = jax.random.normal(ks[3], (1, 8, n, d))
    kp, vp = (jnp.swapaxes(t[0, :1, :, :8], 1, 2) for t in (k4, v4))
    mask = jnp.tril(jnp.ones((8, 8), bool))[None, None]
    got_p = dot_product_attention(qp, kp, vp, mask, dtype=jnp.float32,
                                  scale=scale)
    want_p = np.stack([plain(qp[0, t], kc[0, 0], vc[0, 0], t)
                       for t in range(8)])[None]
    np.testing.assert_allclose(got_p, want_p, atol=1e-5)


# --------------------------------------------------------------------- #
# the whole model against the plain reference
# --------------------------------------------------------------------- #
def test_the_block_is_the_published_one(cfg, bench):
    spec = cfg.block
    assert spec.layer_kinds(10) == ("linear",) * 5 + ("full",) \
        + ("linear",) * 4
    assert spec.linear == LinearMixerSpec.ssd(4, 16, 16)
    mixer = spec.linear
    assert (mixer.rule, mixer.key_heads, mixer.value_heads, mixer.key_dim,
            mixer.value_dim, mixer.conv_taps, mixer.conv_bias) == (
        "ssd", 1, 4, 16, 16, 4, True)
    assert mixer.has_conv and not mixer.has_normaliser
    assert mixer.conv_channels == 2 * 16 + 64
    assert (mixer.state_shape, mixer.state_heads, mixer.state_rows,
            mixer.state_floats) == ((1, 16, 64), 4, 16, 16 * 64)
    assert (spec.positions, spec.embedding_multiplier,
            spec.residual_multiplier, spec.logits_scaling,
            spec.softmax_scale) == ("none", 0.5, 0.22, 2.0, 1.5)
    assert cfg.softmax_scale == 1.5
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 16)
    # the cell's own: one [128, 4096] matrix a slot and layer, 4,352
    # channels under the convolution, 2.10 MB of float32
    full = bench.load_module("builders", BUILDER).transformer_config(
        bench.config_of(bench.benchmark_spec(), {"name": NAME,
                                                 "config": NAME}))
    big = full.block.linear
    assert big == LinearMixerSpec.ssd(64, 64, 128, groups=1, conv_taps=4,
                                      conv_bias=True)
    assert (big.state_shape, big.conv_channels, big.state_floats * 4) == (
        (1, 128, 4096), 4352, 2_097_152)
    assert full.block.layer_kinds(40).count("full") == 4
    assert [l for l, k in enumerate(full.block.layer_kinds(40))
            if k == "full"] == [5, 15, 25, 35]
    assert full.softmax_scale == 0.015625 and full.head_dim == 64
    # the other rules' specs are what they were
    delta = LinearMixerSpec(16, 32, 128, 128)
    assert (delta.rule, delta.state_shape, delta.state_heads,
            delta.state_rows, delta.has_conv, delta.has_normaliser,
            delta.conv_bias) == (
        "delta", (32, 128, 128), 32, 128, True, False, False)
    assert delta == LinearMixerSpec(16, 32, 128, 128, conv_taps=4,
                                    gate="head", gate_floor=0.0)
    kept = LinearMixerSpec.retention(8, 128)
    assert (kept.state_shape, kept.has_conv, kept.conv_bias) == (
        (8, 65, 128, 128), False, False)
    assert BlockSpec().softmax_scale is None \
        and TransformerConfig().softmax_scale == 64 ** -0.5


@pytest.mark.parametrize("change,message", [
    (dict(layer_period=("linear",), kv_heads=2, head_dim=16),
     "qk_norm, kv_heads and head_dim are attention's"),
    (dict(layer_period=("linear",), kv_heads=None, head_dim=None,
          positions="rope"), "rotary positions turn attention's q and k"),
    (dict(layer_period=("linear",), kv_heads=None, head_dim=None,
          norm="rmsnorm", qk_norm=True),
     "qk_norm, kv_heads and head_dim are attention's"),
    (dict(positions="alibi"), "one of"),
    (dict(residual_multiplier=0.0), "are positive"),
    (dict(softmax_scale=-1.0), "are positive"),
])
def test_block_spec_refuses_what_it_cannot_mean(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(cfg.block, **change)


def test_ssd_spec_refuses():
    with pytest.raises(ValueError, match="groups divide its heads"):
        LinearMixerSpec.ssd(6, 16, 16, groups=4)
    with pytest.raises(ValueError, match="one scalar a head"):
        LinearMixerSpec(1, 4, 16, 16, rule="ssd", gate="channel",
                        gate_floor=-5.0)
    with pytest.raises(ValueError, match="at least two taps"):
        LinearMixerSpec.ssd(4, 16, 16, conv_taps=1)
    with pytest.raises(ValueError, match="retention's degree"):
        LinearMixerSpec(1, 4, 16, 16, rule="ssd", power=2)
    with pytest.raises(ValueError, match="conv_bias is the state-space"):
        LinearMixerSpec(2, 4, 16, 16, conv_bias=True)
    with pytest.raises(ValueError, match="one of"):
        LinearMixerSpec(2, 2, 16, 16, rule="mamba")


@pytest.mark.parametrize("length", [1, 40])
def test_sequential_logits_match_the_reference(ref, rc, cfg, params, length):
    tokens = jax.random.randint(jax.random.PRNGKey(length), (2, length), 0,
                                cfg.vocab_size)
    got = jax.jit(lambda p: lm.sequential_logits(cfg, p, tokens))(params)
    want = ref.forward(params, tokens, rc)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_biases_a_skip_and_two_groups_of_order_one(bench, ref, rc):
    """The chip's weights make the convolution's bias and ``dt_bias`` 0
    and ``D`` tiny; here they are of order 1, over two groups of B and
    C, and the program still is the reference."""
    two = _short(rc, mamba_n_groups=2)
    cfg = _cfg_of(bench, two)
    params = _fill(ref.param_shapes(two), seed=4)
    assert jax.tree.map(jnp.shape, params) == lm.param_shapes(cfg)
    mixer = params["stages"]["linear_attention"]
    mixer["D"] = mixer["D"] + 1.0
    mixer["dt_bias"] = mixer["dt_bias"] - 0.7
    mixer["conv"]["bias"] = mixer["conv"]["bias"] + 0.4
    assert cfg.block.linear.state_shape == (2, 16, 32)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 37), 0,
                                cfg.vocab_size)
    want = ref.forward(params, tokens, two)
    logits = jax.jit(lambda p: lm.sequential_logits(cfg, p, tokens))
    np.testing.assert_allclose(logits(params), want, atol=LOGIT_TOL, rtol=0)
    served = _serve(cfg, params, _requests(5))
    assert _gap(ref, two, params, served) <= LOGIT_TOL
    # each of the three is seen: without it the logits move
    for leaf, sub in (("D", None), ("dt_bias", None), ("conv", "bias")):
        broken = jax.tree.map(lambda a: a, params)
        m = broken["stages"]["linear_attention"]
        if sub:
            m[leaf][sub] = jnp.zeros_like(m[leaf][sub])
        else:
            m[leaf] = jnp.zeros_like(m[leaf])
        assert float(jnp.abs(logits(broken) - want).max()) \
            > 30 * LOGIT_TOL, leaf


def test_prefill_then_decode_through_the_cache(ref, rc, cfg, params):
    """One period of ten layers; ragged admissions on three slots, every
    slot reused after an eviction: each served token is the reference's
    first choice at its position, over the whole of every request; the
    manager says what it holds of each kind of state."""
    telemetry.reset()
    requests = _requests()
    served = _serve(cfg, params, requests)
    assert [len(t) for _, t in served] == [o for _, o in requests]
    assert _gap(ref, rc, params, served) <= LOGIT_TOL
    counts = {m["name"]: m["value"]
              for m in telemetry.get().registry.snapshot() if "value" in m}
    assert counts["engine/cache_layers"] == 1
    # one attention layer: keys and values of 2 heads of 16, float32
    assert counts["engine/kv_bytes_per_token"] == 2 * 2 * 16 * 4
    # 9 layers x (16 x 64 float32 + a tail of 3 x 96 float32)
    per_slot = 9 * (16 * 64 * 4 + 3 * 96 * 4)
    assert counts["engine/state_bytes_per_slot"] == per_slot
    assert counts["kv/state_bytes"] == 3 * per_slot
    assert counts["kv/state_rows"] == 16
    assert counts["kernel/ssd_step_elected"] == 0       # the CPU
    assert "kernel/ssd_step_calls" not in counts
    assert "kernel/delta_step_elected" not in counts
    assert counts["engine/state_rows"] > 0
    assert counts["engine/state_prompts"] == 9 * len(requests)
    assert counts["engine/state_prompts_blank"] == 9 * len(requests)


def test_the_fused_kernels_serve_the_same_tokens(bench, ref, rc):
    """A width the state kernel takes (8 heads of 16: one lane tile) and
    both kernels forced under the interpreter: the state step and the
    grouped dense decode at a scale that is no power of two serve the
    reference's tokens."""
    wide = _short(rc, mamba_n_heads=8, mamba_expand=2)
    cfg = _cfg_of(bench, wide)
    params = _fill(ref.param_shapes(wide), seed=2)
    telemetry.reset()
    served = _serve(cfg, params, _requests(4),
                    kernel={"ssd_step": True, "flash_decode": True})
    assert _gap(ref, wide, params, served) <= LOGIT_TOL
    counts = {m["name"]: m["value"]
              for m in telemetry.get().registry.snapshot() if "value" in m}
    assert counts["kernel/ssd_step_elected"] == 1
    assert counts["kernel/ssd_step_calls"] >= 1
    assert counts["kernel/flash_decode_elected"] == 1


def test_what_a_slot_holds(cfg, params):
    engine = ServingEngine(cfg, params, num_slots=3, max_len=48,
                           prefill_len=16, decode_steps=4)
    state = engine.cache.state
    assert state.norm is None
    assert state.ssm.shape == (9, 3, 1, 16, 64)
    assert state.conv.shape == (9, 3, 3 * 96)
    assert state.ssm.dtype == jnp.float32
    assert engine.cache.k.shape == (1, 3, 2, 48, 16)
    assert engine.cache_layers == 1 and engine.linear_layers == 9
    assert kv_cache.bytes_held((1, 3, 2, 16, 48), jnp.bfloat16,
                               (9, cfg.block.linear)) == {
        "kv_bytes_per_token": 2 * 2 * 16 * 2,
        "state_bytes_per_slot": 9 * (16 * 64 * 4 + 3 * 96 * 2)}
    # the cell's: 75.5 MB of state and 0.94 MB of tails a slot
    big = LinearMixerSpec.ssd(64, 64, 128)
    held = kv_cache.bytes_held((4, 64, 8, 64, 3072), jnp.bfloat16, (36, big))
    assert held == {"kv_bytes_per_token": 8192,
                    "state_bytes_per_slot": 36 * (2_097_152 + 26_112)}


@pytest.mark.parametrize("plant", [
    "stale_state", "stale_tail", "no_skip", "norm_before_gate",
    "no_softplus", "residual_one", "head_scale", "rotary",
    "no_embedding_multiplier"])
def test_a_planted_fault_reads_far_above_a_sound_run(ref, rc, bench, plants,
                                                     plant):
    """Each fault of ``benchmark/tools/planted_ssd.py`` under the engine,
    at three layers (a state-space layer, an attention layer, a
    state-space layer)."""
    short = _short(rc)
    params = _fill(ref.param_shapes(short))
    with plants[plant]():
        served = _serve(_cfg_of(bench, short), params, _requests())
    assert _gap(ref, short, params, served) > 30 * LOGIT_TOL


def test_an_evicted_slots_state_is_overwritten_whole(bench, ref, rc):
    """A slot that held a long request, then a short one: the state and
    the tail the second prefill leaves are the ones it leaves in a fresh
    engine, bit for bit — nothing of the previous occupant is read."""
    short = _short(rc)
    cfg = _cfg_of(bench, short)
    params = _fill(ref.param_shapes(short))

    def admit(engine, prompt):
        prompts = np.zeros((2, 16), np.int32)
        prompts[1, :len(prompt)] = prompt
        engine.prefill(prompts, np.array([0, len(prompt)]),
                       np.array([False, True]))
        return [np.asarray(a) for a in engine._state_args()]

    make = lambda: ServingEngine(cfg, params, num_slots=2, max_len=48,
                                 prefill_len=16, decode_steps=4)
    r = np.random.default_rng(0)
    long, short_p = r.integers(0, 509, 16), r.integers(0, 509, 3)
    used = make()
    admit(used, long)
    used.decode(np.array([False, True]))
    for a, b in zip(admit(used, short_p), admit(make(), short_p)):
        assert (a[:, 1] == b[:, 1]).all()


@pytest.mark.parametrize("knob,message", [
    (dict(kv_layout="paged"), r"state-space \(ssd\)"),
    (dict(prefill_chunk=16, kv_block_len=16), r"state-space \(ssd\)"),
    (dict(prefix_caching=True), r"state-space \(ssd\)"),
    (dict(speculative=2), r"state-space \(ssd\)"),
    (dict(tensor_parallel=2), r"state-space \(ssd\) layer's groups"),
])
def test_engine_options_the_block_refuses_by_name(cfg, params, knob,
                                                  message):
    with pytest.raises(ValueError, match=message):
        ServingEngine(cfg, params, num_slots=2, max_len=48, prefill_len=16,
                      **knob)


def test_the_handoff_refuses_the_state(cfg, params):
    from autodist_tpu.serving.disagg import check_handoff_block

    engine = ServingEngine(cfg, params, num_slots=2, max_len=48,
                           prefill_len=16)
    with pytest.raises(ValueError, match="recurrent state"):
        check_handoff_block(engine)


def test_a_decode_step_wears_the_scopes_the_metrics_read(cfg, params):
    """The kernel's call (forced under the interpreter's election) sits
    inside ``linear_attention/state_update``, the convolution and the
    tail's cut inside ``linear_attention/state_conv``, and the scope is of
    the vocabulary."""
    import re

    assert "state_conv" in telemetry.SCOPES
    mixer = LinearMixerSpec.ssd(8, 16, 16)
    layout = kv_cache.DenseLayout((0, 2, 1, 16, 8), {"ssd_step": True},
                                  recurrent=(2, mixer))
    ops = [jnp.asarray(t[:, 0]) for t in _operands(3, 2, 1, 8, 16, 1, 16)]
    ssm = jnp.zeros((2, 2, *mixer.state_shape), jnp.float32)

    def step(ssm):
        with telemetry.scope("linear_attention"):
            return layout.advance_ssd(*ops, ssm, 1)

    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(step).lower(ssm)
                           .as_text(debug_info=True)))
    worn = [n for n in names if "adtk_ssd_step" in n]
    assert worn and all("linear_attention/state_update/adtk_ssd_step/" in n
                        for n in worn)
    chunk = _mixer_chunk(cfg, params)
    x = jnp.ones((2, 1, cfg.hidden_size), jnp.float32)
    lowered = jax.jit(lambda x, state: lm.ssd_attention(
        cfg, chunk, x, state)).lower(x, lm.blank_linear_state(cfg, 2))
    names = set(re.findall(r'loc\("([^"]*)"',
                           lowered.as_text(debug_info=True)))
    assert any("linear_attention/state_conv/" in n for n in names)
    assert any(n.endswith("linear_attention/state_update/mul")
               for n in names)
    # the convolution lies outside the state's scope
    assert not any("state_update/state_conv" in n
                   or "state_conv/state_update" in n for n in names)


def test_report_check_knows_the_gauge_and_the_counter(tmp_path):
    import importlib
    import json
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tools")
    sys.path.insert(0, tools)
    try:
        report = importlib.import_module("telemetry_report")
    finally:
        sys.path.pop(0)
    state = {"kind": "gauge", "name": "engine/state_bytes_per_slot",
             "value": 76437504}
    gauge = lambda v: {"kind": "gauge", "name": "kernel/ssd_step_elected",
                       "value": v}
    calls = {"kind": "counter", "name": "kernel/ssd_step_calls", "value": 36}

    def problems(records):
        with open(os.path.join(tmp_path, "metrics.jsonl"), "w") as f:
            f.write("\n".join(json.dumps(r) for r in records) + "\n")
        return report.check_schema(str(tmp_path))

    assert problems([state, gauge(1), calls]) == []
    assert problems([state, gauge(0)]) == []
    assert any("1 (the fused kernel) or 0" in p
               for p in problems([state, gauge(2)]))
    assert any("holds a recurrent state" in p for p in problems([gauge(1)]))
    assert any("kernel/ssd_step_calls without" in p
               for p in problems([state, gauge(0), calls]))
