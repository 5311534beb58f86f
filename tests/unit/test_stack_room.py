"""``utils/stack_room.py``: a call gets a frame-stack chunk of its own, so
that a hot loop under it (jax's lowering of a large program) cannot
straddle a chunk's end, and the serving engine's programs take their
first call through it."""
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

from autodist_tpu.models.pipeline_lm import make_pipeline_lm_trainable
from autodist_tpu.models.transformer import TransformerConfig
from autodist_tpu.serving import ContinuousBatcher, ServingEngine
from autodist_tpu.utils import stack_room
from autodist_tpu.utils.stack_room import (FirstCallWithRoom,
                                           call_with_stack_room)

# CPython's chunk: pycore_pystate / pystate.c, DATA_STACK_CHUNK_SIZE
CHUNK_BYTES = 16 * 1024


def _callers():
    f, names = sys._getframe(1), []
    while f is not None:
        names.append(f.f_code.co_name)
        f = f.f_back
    return names


def test_arguments_results_and_errors_pass_through():
    assert call_with_stack_room(divmod, 17, 5) == (3, 2)
    assert call_with_stack_room(sorted, [3, 1, 2], reverse=True) == [3, 2, 1]
    with pytest.raises(ZeroDivisionError):
        call_with_stack_room(divmod, 1, 0)


def test_the_call_runs_under_a_frame_that_owns_a_large_chunk():
    def work():
        return _callers()

    assert call_with_stack_room(work)[1] == "_roomy"
    code = stack_room._trampoline.__code__
    frame_bytes = 8 * (code.co_nlocals + code.co_stacksize)
    # larger than a chunk, so the interpreter gives the frame a chunk of
    # its own, rounded up to a power of two: at least as much again free
    # as a deep lowering uses (~150 frames of ~50 slots)
    assert frame_bytes > CHUNK_BYTES
    chunk = CHUNK_BYTES
    while chunk < frame_bytes + 64:
        chunk *= 2
    assert chunk - frame_bytes > 8 * 150 * 50 * 2
    # and the dead names cost the call nothing to run
    assert len(code.co_code) < 64


def test_only_the_first_call_takes_the_detour():
    seen = []

    def fn(x, scale=1):
        seen.append("_roomy" in _callers())
        return x * scale

    f = FirstCallWithRoom(fn)
    assert [f(2), f(3, scale=2), f(4)] == [2, 6, 4]
    assert seen == [True, False, False]


def test_a_jitted_function_keeps_its_own_methods():
    f = FirstCallWithRoom(jax.jit(lambda x: x + 1))
    assert "stablehlo.add" in f.lower(jnp.ones(3)).as_text()
    assert float(f(jnp.ones(()))) == 2.0
    assert f.fn._cache_size() == 1
    assert float(f(jnp.zeros(()))) == 1.0
    assert f.fn._cache_size() == 1


def test_the_engines_programs_trace_with_room():
    cfg = TransformerConfig(
        vocab_size=33, hidden_size=16, num_layers=2, num_heads=2,
        mlp_dim=32, max_len=24, dtype=jnp.float32, dropout_rate=0.0,
        attention_dropout_rate=0.0)
    params = make_pipeline_lm_trainable(
        cfg, optax.sgd(0.1), jax.random.PRNGKey(0)).params
    engine = ServingEngine(cfg, params, num_slots=2, max_len=cfg.max_len,
                           prefill_len=8, decode_steps=3)
    traced_under = {}
    for name in ("_layer_prefill", "_layer_cached"):
        inner = getattr(engine, name)

        def spy(*a, _inner=inner, _name=name, **k):
            traced_under.setdefault(_name, "_roomy" in _callers())
            return _inner(*a, **k)

        setattr(engine, name, spy)
    assert isinstance(engine._prefill_jit, FirstCallWithRoom)
    assert isinstance(engine._decode_jit, FirstCallWithRoom)
    batcher = ContinuousBatcher(engine)
    batcher.submit([1, 2, 3], max_new_tokens=4)
    for _ in range(4):
        batcher.step()
    assert len(batcher.completions) == 1
    assert traced_under and all(traced_under.values()), traced_under
