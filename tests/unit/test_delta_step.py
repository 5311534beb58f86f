"""The delta-step kernel (``kernel/pallas/delta_step.py``): one position
of the gated delta rule over the cache manager's stacked float32 state,
in place — under the Pallas interpreter against the composed
``gated_delta_step``, which stays the CPU path, the golden and the
reference; the election (``serving/kv_cache.py``'s seam, the kernel
slot's ``delta_step``); and the engine's decode with the kernel forced
against the composed decode.  What Mosaic makes of the kernel at the
benchmark's shape is ``tests/unit/test_tpu_compile.py``'s.
"""
import dataclasses
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.kernel.pallas import delta_step as ds
from autodist_tpu.models import pipeline_lm as lm
from autodist_tpu.models.transformer import LinearMixerSpec
from autodist_tpu.serving import ServingEngine, kv_cache
from tests.unit.test_hybrid_block import (_fill, _requests, _serve, bench,
                                          cfg, rc)  # noqa: F401

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _operands(layers, slots, heads, dk=128, dv=128, seed=0,
              dtype=jnp.float32):
    """A decode step's operands as ``linear_attention`` makes them (keys
    and queries normalised, the log decay <= 0, the write strength in
    (0, 1)) and a stacked state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda i, *shape: jax.random.normal(ks[i], shape, jnp.float32)
    q = lm._l2_normalise(n(0, slots, heads, dk)) * dk ** -0.5
    k = lm._l2_normalise(n(1, slots, heads, dk))
    g = -jax.nn.softplus(n(3, slots, heads))
    beta = jax.nn.sigmoid(n(4, slots, heads))
    ssm = n(5, layers, slots, heads, dk, dv).astype(dtype)
    return (q, k, n(2, slots, heads, dv), g, beta), ssm


# a small aligned shape; the benchmark's tile shape, 32 value heads of
# [128, 128] a slot, at two counts of heads a grid step can take there
@pytest.mark.parametrize("layers,slots,heads,layer,hb", [
    (3, 2, 8, 1, None), (2, 1, 32, 0, 16), (2, 2, 32, 1, 8),
], ids=["small", "cell-tiles-16", "cell-tiles-8"])
def test_kernel_agrees_with_the_composed_step(layers, slots, heads, layer,
                                              hb):
    row, ssm = _operands(layers, slots, heads)
    want_o, want_s = lm.gated_delta_step(*row, ssm[layer])
    o, new = ds.gated_delta_step_fused(*row, ssm, layer, heads_per_step=hb,
                                       interpret=True)
    assert o.dtype == new.dtype == jnp.float32 and new.shape == ssm.shape
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(new[layer], want_s, rtol=1e-5, atol=1e-6)
    # no other layer's tile is touched
    others = np.arange(layers) != layer
    assert np.array_equal(np.asarray(new)[others], np.asarray(ssm)[others])


def test_no_decay_and_no_write_leave_the_state_bit_for_bit():
    (q, k, v, g, beta), ssm = _operands(2, 3, 8)
    o, new = ds.gated_delta_step_fused(q, k, v, 0 * g, 0 * beta, ssm, 1,
                                       interpret=True)
    assert np.array_equal(np.asarray(new), np.asarray(ssm))
    # and the read-out is the state's own: S^T q
    np.testing.assert_allclose(
        o, jnp.einsum("bhkv,bhk->bhv", ssm[1], q, precision="highest"),
        rtol=1e-5, atol=1e-6)


def test_layer_is_an_operand():
    """One lowering serves every layer: the layer is a traced scalar."""
    row, ssm = _operands(3, 2, 8)
    step = jax.jit(lambda ssm, layer: ds.gated_delta_step_fused(
        *row, ssm, layer, interpret=True))
    for layer in range(3):
        _, new = step(ssm, jnp.int32(layer))
        changed = [not np.array_equal(np.asarray(new[l]), np.asarray(ssm[l]))
                   for l in range(3)]
        assert changed == [l == layer for l in range(3)]
    assert step._cache_size() == 1


def test_a_state_that_is_not_float32_is_refused_not_cast():
    """The state is float32 in the cache manager and inside the kernel.
    A narrower one would halve the step's bytes and read as a gain the
    benchmark's ``correct`` cannot tell from a sound run (PERF.md
    section 7 (a)): the kernel refuses it by name, the election declines
    it, and the seam then serves it composed, in its own type."""
    row, ssm = _operands(2, 2, 8, dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="float32 state"):
        ds.gated_delta_step_fused(*row, ssm, 0, interpret=True)
    assert not ds.delta_step_elected(True, ssm.shape, ssm.dtype, "tpu")
    layout = kv_cache.DenseLayout((1, 2, 1, 8, 16), {"delta_step": True})
    assert not layout.state_kernel(ssm)
    text = str(jax.make_jaxpr(lambda s: layout.advance_state(*row, s, 0))(
        ssm))
    assert "pallas_call" not in text
    assert layout.advance_state(*row, ssm, 0)[1].dtype == jnp.bfloat16
    # what the manager allocates, whatever the activations' type
    held = kv_cache.init_state(2, 2, LinearMixerSpec(2, 8, 128, 128),
                               jnp.bfloat16)
    assert held.ssm.dtype == jnp.float32 and held.conv.dtype == jnp.bfloat16


F32, BF16 = jnp.float32, jnp.bfloat16


# word (the kernel slot's), [heads,] [dk, dv], state type, backend ->
# elected.  (A window of several positions never reaches the election:
# ``test_a_window_never_reaches_the_step``; a stack with linear layers
# under ``tensor_parallel`` is refused where the engine is built.)
@pytest.mark.parametrize("word,tile,dtype,backend,elected", [
    (None, (128, 128), F32, "tpu", True),       # the cell's decode
    (None, (128, 128), F32, "cpu", False),      # off the TPU
    (True, (128, 128), F32, "cpu", True),       # forced: the interpreter
    (False, (128, 128), F32, "tpu", False),     # forbidden
    (None, (64, 128), F32, "tpu", False),       # dk under a tile
    (None, (128, 192), F32, "tpu", False),      # dv not whole tiles
    (True, (16, 16), F32, "tpu", False),
    (None, (256, 128), F32, "tpu", True),
    (None, (128, 128), BF16, "tpu", False),     # never cast
    (True, (128, 128), BF16, "tpu", False),
    (None, (16, 128, 128), F32, "tpu", True),
    (None, (4, 128, 128), F32, "tpu", False),   # heads not in eights
])
def test_election(word, tile, dtype, backend, elected):
    heads, dk, dv = (32, *tile)[-3:]
    assert ds.delta_step_elected(word, (12, 32, heads, dk, dv), dtype,
                                 backend) == elected


def test_the_backend_is_observed_where_none_is_given(monkeypatch):
    shape = (12, 32, 32, 128, 128)
    assert not ds.delta_step_elected(None, shape, F32)      # the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ds.delta_step_elected(None, shape, F32)
    assert kv_cache.DenseLayout((1, 2, 1, 8, 16), {}).state_kernel(
        jax.ShapeDtypeStruct(shape, F32))
    assert not kv_cache.DenseLayout(
        (1, 2, 1, 8, 16), {"delta_step": False}).state_kernel(
            jax.ShapeDtypeStruct(shape, F32))


def test_a_window_never_reaches_the_step(cfg):
    """``linear_attention`` hands one position to ``step`` and a longer
    window to the chunked form, whatever ``step`` is."""
    wide = _wide(cfg)
    shapes = lm.param_shapes(wide)
    chunk = lm.layer_chunk(wide, _fill(_as_leaves(shapes))["stages"], 0)
    called = []

    def step(*a):
        called.append(a[-1].shape)
        return lm.gated_delta_step(*a)

    for positions in (5, 1):
        x = jnp.ones((2, positions, wide.hidden_size), jnp.float32)
        lm.linear_attention(wide, chunk, x, lm.blank_linear_state(wide, 2),
                            step=step)
    assert called == [(2, 8, 128, 128)]


def _wide(cfg):
    """``test_hybrid_block.py``'s small stack with linear heads of the
    kernel's tiles: 2 key heads, 8 value heads of ``[128, 128]``."""
    return dataclasses.replace(cfg, block=dataclasses.replace(
        cfg.block, linear=LinearMixerSpec(2, 8, 128, 128)))


def _as_leaves(shapes):
    return jax.tree.map(lambda s: (s, "float32"), shapes,
                        is_leaf=lambda x: isinstance(x, tuple))


def _gauges():
    return {m["name"]: m["value"]
            for m in telemetry.get().registry.snapshot()
            if m["kind"] == "gauge"}


def test_engine_decode_with_the_kernel_serves_the_composed_tokens(cfg):
    """The engine's prefill then fused decode, the recurrent state
    advanced in place by the kernel (forced: the interpreter), serve the
    greedy tokens of the composed decode, request for request; the gauge
    says which way each engine went."""
    wide = _wide(cfg)
    params = _fill(_as_leaves(lm.param_shapes(wide)))
    requests = _requests(n=5)
    served = {}
    for word in (False, True):
        telemetry.reset()
        try:
            served[word] = _serve(wide, params, requests,
                                  kernel={"delta_step": word})
            assert _gauges()["kernel/delta_step_elected"] == int(word)
        finally:
            telemetry.reset()
    for (_, composed), (_, fused) in zip(served[False], served[True]):
        assert np.array_equal(composed, fused)


def test_engine_elects_by_what_it_observes(cfg, monkeypatch):
    """No word in the kernel slot: the composed step on the CPU and for
    tiles the kernel cannot take, the kernel under a TPU; the manager's
    state is float32 either way."""
    wide = _wide(cfg)
    params = _fill(_as_leaves(lm.param_shapes(wide)))
    narrow = _fill(_as_leaves(lm.param_shapes(cfg)))
    kw = dict(num_slots=2, max_len=48, prefill_len=16, decode_steps=4)
    for backend, which, p, elected in (("cpu", wide, params, 0),
                                       ("tpu", wide, params, 1),
                                       ("tpu", cfg, narrow, 0)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        telemetry.reset()
        try:
            # flash_decode is not this test's: the stack's grouped heads
            # decode through cached_attention on any backend
            engine = ServingEngine(which, p, **kw)
            assert _gauges()["kernel/delta_step_elected"] == elected
        finally:
            telemetry.reset()
        assert engine.cache.state.ssm.dtype == jnp.float32
        assert engine.kv.state_kernel(engine.cache.state.ssm) == bool(elected)


def test_an_engine_without_linear_layers_says_nothing():
    from tests.unit.test_flash_decode_dense import _lm_cfg, _lm_params

    plain = _lm_cfg(hidden_size=128, max_len=64)
    telemetry.reset()
    try:
        ServingEngine(plain, _lm_params(plain), num_slots=2, max_len=64,
                      prefill_len=8, kernel={"delta_step": True})
        assert "kernel/delta_step_elected" not in _gauges()
    finally:
        telemetry.reset()


def test_the_kernel_slot_knows_the_name():
    from autodist_tpu.kernel.pallas import (KERNEL_CHOICES, OBSERVED_KERNELS,
                                            kernel_marker)
    from autodist_tpu.strategy.ir import normalize_kernel

    assert "delta_step" in KERNEL_CHOICES and "delta_step" in OBSERVED_KERNELS
    assert kernel_marker("delta_step") == "adtk_delta_step"
    # an observed kernel keeps the word that forbids it
    assert normalize_kernel({"delta_step": False}) == {"delta_step": False}
    assert normalize_kernel("delta_step") == {"delta_step": True}
    assert normalize_kernel({"delta_step": None}) == {}


def test_the_call_wears_the_scopes_the_roofline_reads():
    """``linear_attention/state_update/adtk_delta_step``: the benchmark's
    ``decode_state_update_roofline_pct`` finds the kernel, and the small
    ops that build its operands, by the ``state_update`` component."""
    row, ssm = _operands(2, 2, 8)
    layout = kv_cache.DenseLayout((1, 2, 1, 8, 16), {"delta_step": True})

    def step(ssm):
        with telemetry.scope("linear_attention"):
            return layout.advance_state(*row, ssm, 1)

    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(step).lower(ssm)
                           .as_text(debug_info=True)))
    worn = [n for n in names if "adtk_delta_step" in n]
    assert worn and all("linear_attention/state_update/adtk_delta_step/"
                        in n for n in worn)
    for op in ("exp", "reduce_sum"):      # exp(g), k . q
        assert any(n.endswith(f"linear_attention/state_update/{op}")
                   for n in names), op


def test_report_check_knows_the_gauge(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        report = importlib.import_module("telemetry_report")
    finally:
        sys.path.pop(0)
    state = {"kind": "gauge", "name": "engine/state_bytes_per_slot",
             "value": 25755648}
    gauge = lambda v: {"kind": "gauge", "name": "kernel/delta_step_elected",
                       "value": v}

    def problems(records):
        with open(os.path.join(tmp_path, "metrics.jsonl"), "w") as f:
            f.write("\n".join(json.dumps(r) for r in records) + "\n")
        return report.check_schema(str(tmp_path))

    assert problems([state, gauge(1)]) == []
    assert problems([state, gauge(0)]) == []
    assert problems([state]) == []
    assert any("1 (the fused kernel) or 0" in p
               for p in problems([state, gauge(2)]))
    assert any("holds a recurrent state" in p for p in problems([gauge(1)]))
